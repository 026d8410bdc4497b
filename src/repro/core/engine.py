"""Generic speculative decoding engine over a pluggable ``Proposer``.

``SpecEngine`` runs the paper's static speculation step for *any* proposer
(trained Medusa heads, a draft model, train-free n-gram lookup —
``core/proposers.py``, DESIGN.md §13): candidates from the proposer -> one
backbone verification forward -> tensorized acceptance -> zero-copy commit.
The full generation loop is a single ``lax.while_loop`` over one compiled
step graph — no retraces, no host round-trips; shapes are identical every
iteration (the NPU "Static Shape" contract, natively XLA).  The engine owns
everything proposer-independent: target prefill and suffix-prefill,
verification dispatch (greedy / typical / sample via ``core/verify.py``),
cache construction and commit across dense/paged/fp/int8 layouts, and
``StepStats``.

``ar_generate`` is the autoregressive baseline sharing the same cache
machinery (T=1 decode), used for the paper's speedup/overhead metrics and
for the losslessness test (greedy spec == greedy AR, token for token);
``ar_generate_sampled`` is its stochastic sibling, the distribution-equality
oracle for ``accept="sample"`` (DESIGN.md §11).

Cache storage dtype (``cfg.cache_dtype``, DESIGN.md §10) threads through
every path here implicitly: ``init_cache`` builds the int8 layout, prefill
and the T=1/T=T decode steps quantize on write, ``commit`` re-quantizes the
accepted rows, and the losslessness invariant is preserved because both
engines read identical (fake-quantized) values.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SamplingParams
from repro.core import sampling as S
from repro.core import verify as V
from repro.core.proposers import (MedusaProposer, Proposer, make_proposer)
from repro.core.tree import TreeBuffers, chain_tree
from repro.models import api as model_api
from repro.models.api import get_model


class StepStats(NamedTuple):
    tokens_out: jnp.ndarray      # [B] int32 tokens generated (incl. bonus)
    steps: jnp.ndarray           # scalar int32 decode steps taken
    accepted_sum: jnp.ndarray    # scalar int32 — sum of per-step acc, each
                                 # clamped to the remaining max_new budget
                                 # and excluding the final bonus token, so
                                 # accepted_sum / (steps * B) is the
                                 # unbiased mean accepted length
    accepted_per_slot: Optional[jnp.ndarray] = None
                                 # [B] int32 — the same clamped per-step acc
                                 # summed per row; the per-slot acceptance
                                 # signal adaptive speculation feeds on
                                 # (DESIGN.md §14)


class SpecEngine:
    """Speculative engine for one (config, proposer) pair.

    ``proposer`` selects the draft policy (``core/proposers.py``); passing
    a ``TreeBuffers`` as ``tb`` (or nothing) keeps the legacy behaviour of
    a ``MedusaProposer`` on that tree.  ``accept`` selects verification:
    "greedy" (lossless argmax match), "typical" (Medusa's lossy typical
    acceptance) or "sample" (lossless stochastic rejection-sampling
    verification under ``sampling`` — DESIGN.md §11, dispatched per the
    proposer's ``q_kind``).  At ``sampling.temperature <= 0`` the "sample"
    mode is token-identical to "greedy".
    """

    def __init__(self, cfg: ModelConfig, tb: Optional[TreeBuffers] = None,
                 use_kernel: bool = False, accept: str = "greedy",
                 temperature: float = 0.7, deferred: bool = False,
                 sampling: Optional[SamplingParams] = None,
                 proposer: Optional[Proposer] = None,
                 verify_fusion: Optional[bool] = None):
        if accept not in ("greedy", "typical", "sample"):
            raise ValueError(f"unknown accept mode {accept!r}")
        if proposer is not None and tb is not None:
            raise ValueError("pass either tb (legacy Medusa tree) or "
                             "proposer, not both")
        # resolve the fusion knob into the config itself: the model's decode
        # path gates the fused write side on ``cfg.verify_fusion``
        # (DESIGN.md §15), so an engine-level override must be visible there
        vf = cfg.verify_fusion if verify_fusion is None else verify_fusion
        if vf != cfg.verify_fusion:
            cfg = dataclasses.replace(cfg, verify_fusion=vf)
        self.cfg = cfg
        self.model = get_model(cfg)
        self.proposer = proposer if proposer is not None \
            else MedusaProposer(cfg, tb)
        self.tb = self.proposer.tb
        if cfg.spec_mode == "chain" and not self.tb.is_chain:
            raise ValueError(
                f"{cfg.name}: SSM/hybrid archs verify in CHAIN mode "
                "(DESIGN.md §4); pass a chain_tree().")
        self.dtree = self.proposer.dtree
        self.use_kernel = use_kernel
        self.deferred = deferred and cfg.family != "encdec"
        self.accept = accept
        self.temperature = temperature
        self.sampling = sampling if sampling is not None else \
            SamplingParams(temperature=temperature)
        self.verify_fusion = vf
        if self.verify_fusion:
            # the fused epilogue carries Verdict-sized statistics only
            # (DESIGN.md §15): typical acceptance needs full-row entropies,
            # and top-k/top-p warps need the sorted row — neither survives
            # the [B, T, V]-free contract, so they stay unfused.
            if self.accept == "typical":
                raise ValueError("verify_fusion does not support "
                                 "accept='typical' (DESIGN.md §15)")
            sp = self.sampling
            if self.accept == "sample" and (sp.top_k or sp.top_p != 1.0):
                raise ValueError(
                    "verify_fusion + accept='sample' requires top_k=0 and "
                    "top_p=1.0 (DESIGN.md §15)")
        # TP verify epilogue eligibility (DESIGN.md §18): same statistics
        # contract as the fused kernel — greedy, or untruncated sampling.
        # Ineligible TP engines fall back to the all-gathered full-logits
        # walk inside the shard_map body (correct, just not [B,T,V]-free).
        sp = self.sampling
        self._tp_stats = bool(cfg.tp_axis) and not self.verify_fusion and (
            self.accept == "greedy"
            or (self.accept == "sample"
                and not sp.top_k and sp.top_p == 1.0))

    def _sampling_args(self, temperature=None, top_p=None):
        """(temperature, top_k, top_p) with engine defaults, per-call (or
        per-slot array) overrides winning."""
        sp = self.sampling
        return (sp.temperature if temperature is None else temperature,
                sp.top_k,
                sp.top_p if top_p is None else top_p)

    def init_cache(self, batch: int, max_len: int, n_blocks=None):
        """Decode cache for ``batch`` slots via the layout-aware factory
        (``models.api.init_cache``): honours ``cfg.cache_dtype`` (int8
        layout halves cache bytes per slot — DESIGN.md §10) and
        ``cfg.cache_layout`` (``n_blocks`` sizes the paged pool; None means
        the allocator-free identity table — DESIGN.md §12)."""
        return model_api.init_cache(self.cfg, batch, max_len,
                                    n_blocks=n_blocks)

    def init_proposer_state(self, batch: int, capacity: int):
        """Fresh proposer device state for ``batch`` rows holding up to
        ``capacity`` tokens each (history buffers, draft caches — sized
        once, static thereafter; DESIGN.md §13)."""
        return self.proposer.init_state(batch, capacity)

    def _tok_lens(self, lengths, extra_embeds):
        """True token counts inside the prompt tensor: ``lengths`` minus
        the frontend-embedding prefix a VLM/audio prefill prepends."""
        if extra_embeds is not None and self.cfg.frontend \
                and self.cfg.family != "encdec":
            return lengths - self.cfg.frontend_len
        return lengths

    # -- one-shot pieces (jit-friendly pure functions) ----------------------

    def prefill(self, params, proposer_params, tokens, lengths, cache,
                extra_embeds=None, key=None, temperature=None, top_p=None,
                state=None):
        """-> (cache, lengths, base_token [B], proposer state).

        Under ``accept="sample"`` (and a ``key``), the base token — the
        first emitted token — is *sampled* from the warped target logits,
        matching the stochastic AR oracle; otherwise argmax.
        ``temperature``/``top_p`` may be per-row [B] arrays (the serving
        scheduler's per-request values).  ``state`` is the proposer state
        to prime; None allocates one sized for the prompt plus a few steps
        (fine for Medusa, too small for a full n-gram/draft generation —
        loops should pass ``init_proposer_state`` with a real budget)."""
        B, Sp = tokens.shape
        last_hidden, cache = self.model.prefill(
            params, self.cfg, tokens, lengths, cache, extra_embeds=extra_embeds)
        logits = self.model.unembed(params, self.cfg, last_hidden)
        if self.accept == "sample" and key is not None:
            t, k, p = self._sampling_args(temperature, top_p)
            base = S.sample(key, logits, t, k, p)
        else:
            base = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if state is None:
            state = self.init_proposer_state(B, Sp + self.dtree.T + 2)
        state = self.proposer.prime(
            proposer_params, state, tokens, lengths,
            self._tok_lens(lengths, extra_embeds), last_hidden, base,
            extra_embeds=extra_embeds)
        return cache, lengths, base, state

    def suffix_prefill(self, params, proposer_params, cache, lengths, tokens,
                       n_valid, active, key=None, temperature=None,
                       top_p=None, state=None):
        """Continue a prefill from cached prefix rows (prefix-cache
        admission, DESIGN.md §12).

        The scheduler maps a request's shared prompt blocks into its slot's
        block table and only the un-cached suffix runs through the model:
        a causal T-token decode over ``tokens`` [B, T] (right-padded
        suffixes) starting at ``lengths`` [B] (the per-slot cached-prefix
        length), committed for ``n_valid`` [B] true suffix rows on slots
        where ``active`` [B] is True — inactive slots keep their lengths
        frozen exactly as in the masked serving step (DESIGN.md §9) and
        their dead writes sink per the paged write rules.

        Returns (cache, lengths, base [B], proposer state) with meaningful
        values on active rows only.  The proposer is primed from the
        *suffix* (history-based proposers start without the shared prefix
        — conservative but lossless; proposers with
        ``supports_prefix=False`` cannot take this path at all).  Sampling
        mirrors ``prefill``: under ``accept="sample"`` with a ``key`` the
        base token is drawn from the warped target logits at the last
        valid suffix position (``temperature``/``top_p`` may be per-row
        [B] arrays); otherwise argmax.
        """
        if not self.proposer.supports_prefix:
            raise ValueError(f"{type(self.proposer).__name__} cannot be "
                             "primed from a prompt suffix (DESIGN.md §13)")
        B, T = tokens.shape
        causal = jnp.tril(jnp.ones((T, T), bool))
        depths = jnp.arange(T, dtype=jnp.int32)
        hidden, spec_cache = self.model.decode(
            params, self.cfg, cache, tokens, lengths, causal, depths,
            use_kernel=self.use_kernel)
        path = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        nv = jnp.clip(n_valid, 1, T)
        cache, new_lengths = self.model.commit(self.cfg, spec_cache, lengths,
                                               path, nv, active=active)
        h_last = jnp.take_along_axis(
            hidden, (nv - 1)[:, None, None], axis=1)[:, 0]        # [B, d]
        logits = self.model.unembed(params, self.cfg, h_last)
        if self.accept == "sample" and key is not None:
            t, k, p = self._sampling_args(temperature, top_p)
            base = S.sample(key, logits, t, k, p)
        else:
            base = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if state is None:
            state = self.init_proposer_state(B, T + self.dtree.T + 2)
        state = self.proposer.prime(proposer_params, state, tokens,
                                    new_lengths, nv, h_last, base)
        return cache, new_lengths, base, state

    def _verify(self, cand, logits, q, key, temperature, top_k, top_p,
                dtree=None):
        """Acceptance-rule dispatch (DESIGN.md §3, §11): the engine picks
        the verifier from (``accept``, proposer ``q_kind``); everything
        downstream of it is shape-identical.  ``dtree`` overrides the
        engine topology for the adaptive-gamma graph family (DESIGN.md
        §14) — every verifier is lossless for ANY proposal topology, so
        switching trees between steps never changes the output stream."""
        dt = self.dtree if dtree is None else dtree
        if self.accept == "typical":
            return V.typical_verify(cand, logits, dt, key,
                                    temperature=self.temperature)
        if self.accept == "sample":
            if self.proposer.q_kind == "logits":
                return V.sample_verify_chain(cand, logits, q, dt,
                                             key, temperature=temperature,
                                             top_k=top_k, top_p=top_p)
            return V.sample_verify_tree(cand, logits, q, dt, key,
                                        temperature=temperature,
                                        top_k=top_k, top_p=top_p)
        return V.greedy_verify(cand, logits, dt)

    def _verify_fused(self, params, cand, hidden, q, key, temperature,
                      top_k, top_p, dtree=None):
        """Fused-epilogue acceptance (DESIGN.md §15): the kernel streams the
        lm-head matmul over vocab blocks and hands back Verdict-sized
        statistics — the [B, T, V] logits tensor never reaches HBM.  The
        residual/bonus distribution is rebuilt from ONE [B, V] row unembed
        at the stopping node; dispatch mirrors ``_verify`` exactly, and the
        verdicts are token-identical (gated by tests/test_verify_fusion.py).
        """
        from repro.kernels import ops as KO
        dt = self.dtree if dtree is None else dtree
        B = cand.shape[0]
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        if self.accept == "sample":
            t_arr = jnp.broadcast_to(
                jnp.asarray(temperature, jnp.float32), (B,))
            tmax = jnp.maximum(t_arr, 1e-6)
        else:
            tmax = jnp.ones((B,), jnp.float32)   # greedy: raw-logit argmax
        stats = V.VerifyStats(*KO.verify_stats(hidden, w, cand, tmax))
        rows = jnp.arange(B)

        def row_fn(idx):
            return self.model.unembed(params, self.cfg, hidden[rows, idx])

        if self.accept == "sample":
            if self.proposer.q_kind == "logits":
                return V.sample_verify_chain_stats(
                    cand, stats, q, dt, key, row_fn,
                    temperature=temperature, top_k=top_k, top_p=top_p)
            return V.sample_verify_tree_stats(cand, stats, q, dt, key,
                                              row_fn, temperature=temperature)
        return V.greedy_verify_stats(cand, stats, dt)

    def _verify_tp(self, params, cand, hidden, q, key, temperature,
                   top_k, top_p, dtree=None):
        """Tensor-parallel acceptance epilogue (DESIGN.md §18).

        Inside the shard_map body the lm_head holds a [d, V/N] vocab slice,
        so each shard computes warped logits over its columns only and the
        ``VerifyStats`` reduction crosses shards with collectives: max via
        a gathered per-shard row-max, first-wins argmax by picking the
        first shard attaining it (shards hold ascending contiguous vocab
        slices, so shard order IS global index order), sumexp via psum of
        rescaled partials, and candidate columns via psum of a one-shard
        one-hot extraction (every candidate token lives on exactly one
        shard, so the sum adds exact zeros).  The full [B, T, V] tensor
        exists on no device — per shard only [B, T, V/N] materialises —
        and the stats feed the same ``*_stats`` walks as the fused kernel
        path, so verdicts are token-identical to the single-device engine.
        """
        dt = self.dtree if dtree is None else dtree
        axis = self.cfg.tp_axis
        B, T = cand.shape
        wv = self.model.unembed_local(params, self.cfg, hidden)  # [B,T,Vloc]
        v_loc = wv.shape[-1]
        if self.accept == "sample":
            t_arr = jnp.broadcast_to(
                jnp.asarray(temperature, jnp.float32), (B,))
            tmax = jnp.maximum(t_arr, 1e-6)
        else:
            tmax = jnp.ones((B,), jnp.float32)   # greedy: raw-logit argmax
        wv = wv.astype(jnp.float32) / tmax[:, None, None]
        offs = jax.lax.axis_index(axis).astype(jnp.int32) * v_loc
        m_loc = jnp.max(wv, axis=-1)                              # [B, T]
        a_loc = jnp.argmax(wv, axis=-1).astype(jnp.int32) + offs
        ms = jax.lax.all_gather(m_loc, axis)                   # [N, B, T]
        am = jax.lax.all_gather(a_loc, axis)                   # [N, B, T]
        first = jnp.argmax(ms, axis=0)       # first shard attaining the max
        m = jnp.max(ms, axis=0)
        argm = jnp.take_along_axis(am, first[None], axis=0)[0]
        l = jax.lax.psum(
            jnp.sum(jnp.exp(wv - m[:, :, None]), axis=-1), axis)
        here = (cand >= offs) & (cand < offs + v_loc)             # [B, T]
        cidx = jnp.clip(cand - offs, 0, v_loc - 1)
        colw = jnp.take_along_axis(
            wv, jnp.broadcast_to(cidx[:, None, :], (B, T, T)), axis=-1)
        cand_w = jax.lax.psum(
            jnp.where(here[:, None, :], colw, 0.0), axis)      # [B, T, T]
        stats = V.VerifyStats(argm, m, l, cand_w)
        rows = jnp.arange(B)

        def row_fn(idx):
            # one [B, V] row, all-gathered by ``unembed`` — the residual /
            # bonus resample never needs more than the stopping node's row
            return self.model.unembed(params, self.cfg, hidden[rows, idx])

        if self.accept == "sample":
            if self.proposer.q_kind == "logits":
                return V.sample_verify_chain_stats(
                    cand, stats, q, dt, key, row_fn,
                    temperature=temperature, top_k=top_k, top_p=top_p)
            return V.sample_verify_tree_stats(cand, stats, q, dt, key,
                                              row_fn, temperature=temperature)
        return V.greedy_verify_stats(cand, stats, dt)

    def lm_head_passes(self, B: int, dtree=None) -> int:
        """Passes over the lm head that the fused verify epilogue makes in
        one step of B slots on ``dtree`` (the engine's tree by default):
        ``cdiv(B, G)`` for the kernel's slot group G
        (``kernels.tree_attention.verify_stats_plan``); 0 without verify
        fusion.  Static per shape, so the host counts it with no sync."""
        if not self.verify_fusion:
            return 0
        from repro.kernels.tree_attention import lm_head_passes
        dt = self.dtree if dtree is None else dtree
        return lm_head_passes(B, dt.T, self.cfg.d_model, self.cfg.vocab_size,
                              self.cfg.dtype)

    def step_dtrees(self, levels=()):
        """The adaptive-speculation graph family (DESIGN.md §14): a small,
        static list of ``(gamma, DeviceTree)`` step topologies, ascending,
        always ending with the proposer's full tree.

        Each level is a single-path ``chain_tree`` prefix — the cheapest
        way to shrink speculation while staying verifiable by every accept
        mode — and the family is fixed at build time so the serving
        scheduler compiles one step graph per level and only *selects*
        host-side (HADES' static-graph-family discipline: adapting depth
        must not mean recompiling).  ``levels`` lists the chain gammas
        (default (1, 3), filtered to < the full tree's K)."""
        K = self.dtree.K
        fam = []
        for g in sorted(set(levels or (1, 3))):
            if 0 < g < K:
                fam.append((g, V.device_tree(chain_tree(g))))
        fam.append((K, self.dtree))
        return fam

    def spec_step(self, params, proposer_params, cache, lengths, base, state,
                  key, active=None, temperature=None, top_p=None, dtree=None):
        """One static speculative step.
        Returns (cache, lengths, verdict, state').

        ``state`` is the proposer's device state (from ``prefill`` /
        ``init_proposer_state``); the step is propose -> one target
        forward -> verify -> commit -> observe, with every stage
        fixed-shape.  ``active`` [B] bool (optional) enables the
        masked-commit variant used by the serving scheduler (DESIGN.md
        §9): all B slots run through the same static graph, but only
        active slots advance their cache length — empty or finished slots
        are masked out of the commit so their state stays frozen until
        admission overwrites the whole slot row.

        ``temperature``/``top_p`` override the engine-level
        ``SamplingParams`` and may be per-slot [B] device arrays.  The
        step ``key`` feeds verification directly for deterministic
        proposers (the legacy PRNG stream) and is split (propose, verify)
        when the proposer draws its own randomness.

        ``dtree`` (optional) overrides the step topology with a member of
        ``step_dtrees()`` — the adaptive-gamma graph family (DESIGN.md
        §14).  The proposer truncates its candidates to the smaller tree
        (a draft model actually runs fewer draft steps) and verification
        stays lossless, so the scheduler may pick a different level every
        step without touching the token stream.
        """
        dt = self.dtree if dtree is None else dtree
        t, k, p = self._sampling_args(temperature, top_p)
        if self.proposer.consumes_key:
            k_prop, k_ver = jax.random.split(key)
        else:
            k_prop = k_ver = key
        cand, q, state = self.proposer.propose(
            proposer_params, state, base, k_prop, t, k, p,
            stochastic=self.accept == "sample", dtree=dt)
        kw = {"deferred": True} if self.deferred else {}
        hidden, spec_cache = self.model.decode(
            params, self.cfg, cache, cand, lengths,
            jnp.asarray(dt.mask), jnp.asarray(dt.depths),
            use_kernel=self.use_kernel, **kw)
        if self.verify_fusion:
            verdict = self._verify_fused(params, cand, hidden, q, k_ver,
                                         t, k, p, dtree=dt)
        elif self._tp_stats:
            verdict = self._verify_tp(params, cand, hidden, q, k_ver,
                                      t, k, p, dtree=dt)
        else:
            logits = self.model.unembed(params, self.cfg, hidden)     # [B, T, V]
            verdict = self._verify(cand, logits, q, k_ver, t, k, p, dtree=dt)
        cache, lengths = self.model.commit(
            self.cfg, spec_cache, lengths, verdict.path_slots, verdict.acc,
            active=active)
        h_last = jnp.take_along_axis(
            hidden, verdict.last_slot[:, None, None], axis=1)[:, 0]   # [B, d]
        state = self.proposer.observe(proposer_params, state, verdict,
                                      h_last, lengths)
        return cache, lengths, verdict, state

    # -- full generation loops ----------------------------------------------

    def generate(self, params, proposer_params, tokens, prompt_lengths, cache,
                 max_new: int, extra_embeds=None, key=None, state=None):
        """Full speculative generation loop — one compiled step graph inside
        a single ``lax.while_loop`` (§2 static-shape contract), identical
        for every proposer.

        tokens [B, S_p] int32 right-padded prompts, prompt_lengths [B]
        int32, cache from ``init_cache`` (any layout/dtype — dense/paged,
        fp/int8).  Returns (out_tokens [B, max_new] int32, n_out [B] int32
        true lengths, StepStats).  ``key`` drives prefill base sampling and
        per-step acceptance draws under ``accept="sample"``.  ``state``
        (optional) is a pre-built proposer state — e.g. a draft cache the
        caller allocated; None allocates one sized for this call."""
        dt = self.dtree
        key = key if key is not None else jax.random.PRNGKey(0)
        B, Sp = tokens.shape
        K1 = dt.K + 1
        buf_len = max_new + K1 + 1
        if state is None:
            state = self.init_proposer_state(B, Sp + max_new + dt.T + 2)
        key, kp = jax.random.split(key)
        cache, lengths, base, state = self.prefill(
            params, proposer_params, tokens, prompt_lengths, cache,
            extra_embeds, key=kp, state=state)
        out = jnp.zeros((B, buf_len), jnp.int32)
        max_steps = max_new  # worst case 1 token/step

        def write_out(out, toks, n_out):
            def one(o, t, s):
                return jax.lax.dynamic_update_slice(o, t, (s,))
            return jax.vmap(one)(out, toks, jnp.minimum(n_out, buf_len - K1))

        def cond(c):
            n_out, steps = c[5], c[6]
            return (steps < max_steps) & jnp.any(n_out < max_new)

        def body(c):
            (cache, lengths, base, state, out, n_out, steps, acc_sum,
             acc_slot, key) = c
            key, sub = jax.random.split(key)
            cache, lengths, verdict, state = self.spec_step(
                params, proposer_params, cache, lengths, base, state, sub)
            out = write_out(out, verdict.path_tokens, n_out)
            # per-step accepted count clamped to the remaining budget: the
            # last step may overshoot max_new, and the bonus token is
            # accounted separately — both would bias mean-accepted-length
            acc_row = jnp.minimum(verdict.acc, jnp.maximum(max_new - n_out, 0))
            acc_sum = acc_sum + jnp.sum(acc_row)
            acc_slot = acc_slot + acc_row
            n_out = n_out + verdict.acc
            return (cache, lengths, verdict.next_token, state, out,
                    n_out, steps + 1, acc_sum, acc_slot, key)

        n_out = jnp.zeros((B,), jnp.int32)
        carry = (cache, lengths, base, state, out, n_out,
                 jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                 jnp.zeros((B,), jnp.int32), key)
        (cache, lengths, base, state, out, n_out, steps, acc_sum, acc_slot,
         _) = jax.lax.while_loop(cond, body, carry)
        # final certain token
        out = write_out(out, jnp.broadcast_to(base[:, None], (B, K1)), n_out)
        n_out = n_out + 1
        stats = StepStats(tokens_out=n_out, steps=steps, accepted_sum=acc_sum,
                          accepted_per_slot=acc_slot)
        return out[:, :max_new], jnp.minimum(n_out, max_new), stats


def build_engine(cfg: ModelConfig, proposer: str = "medusa", *,
                 tb: Optional[TreeBuffers] = None,
                 draft_cfg: Optional[ModelConfig] = None,
                 draft_layers: int = 2, gamma: int = 4, max_n: int = 3,
                 min_n: int = 1, matcher: str = "auto",
                 use_kernel: bool = False,
                 accept: str = "greedy",
                 sampling: Optional[SamplingParams] = None,
                 verify_fusion: Optional[bool] = None) -> SpecEngine:
    """One-stop engine construction shared by the launcher, the benchmarks
    and the tests (DESIGN.md §13).

    ``proposer`` names the draft policy (medusa | draft | ngram).  For
    "draft" a ``draft_cfg`` may be supplied; omitted, a ``draft_layers``-
    layer sibling of ``cfg`` is derived (the classic small-draft setup).
    ``tb`` overrides the Medusa tree (default: ``cfg.spec_mode``'s tree);
    ``gamma``/``max_n``/``min_n`` shape the chain proposers; ``matcher``
    picks the ngram lookup structure (scan | automaton | auto — auto
    switches to the hash-table automaton at history capacity ≥ 8k, where
    the scan's O(max_n · H) compare sweep starts to dominate the step).
    """
    if proposer == "draft" and draft_cfg is None:
        draft_cfg = dataclasses.replace(
            cfg, num_layers=min(draft_layers, cfg.num_layers),
            name=cfg.name + "-draft")
    p = make_proposer(proposer, cfg, tb=tb, draft_cfg=draft_cfg, gamma=gamma,
                      max_n=max_n, min_n=min_n, matcher=matcher)
    return SpecEngine(cfg, use_kernel=use_kernel, accept=accept,
                      sampling=sampling, proposer=p,
                      verify_fusion=verify_fusion)


def ar_generate(cfg: ModelConfig, params, tokens, prompt_lengths, cache,
                max_new: int, extra_embeds=None):
    """Greedy autoregressive baseline on the same cache machinery (T=1).

    tokens [B, S_p] int32, prompt_lengths [B] int32, cache from
    ``init_cache`` (any layout/dtype).  Returns (out [B, max_new] int32,
    lengths [B] int32 final cache lengths)."""
    model = get_model(cfg)
    B = tokens.shape[0]
    chain1 = jnp.ones((1, 1), bool)
    depth0 = jnp.zeros((1,), jnp.int32)

    last_hidden, cache = model.prefill(params, cfg, tokens, prompt_lengths,
                                       cache, extra_embeds=extra_embeds)
    base = jnp.argmax(model.unembed(params, cfg, last_hidden), axis=-1).astype(jnp.int32)
    out = jnp.zeros((B, max_new), jnp.int32)

    def body(i, c):
        cache, lengths, tok, out = c
        out = out.at[:, i].set(tok)
        hidden, cache = model.decode(params, cfg, cache, tok[:, None], lengths,
                                     chain1, depth0)
        # T=1: the written row is already in place; no compaction needed
        lengths = lengths + 1
        # ssm spec states carry a T=1 axis; select it
        cache = _squeeze_spec(model, cfg, cache, lengths)
        nxt = jnp.argmax(model.unembed(params, cfg, hidden[:, 0]), axis=-1)
        return (cache, lengths, nxt.astype(jnp.int32), out)

    cache, lengths, tok, out = jax.lax.fori_loop(
        0, max_new, body, (cache, prompt_lengths, base, out))
    return out, lengths


def ar_generate_sampled(cfg: ModelConfig, params, tokens, prompt_lengths,
                        cache, max_new: int, key,
                        sampling: Optional[SamplingParams] = None,
                        extra_embeds=None):
    """Stochastic autoregressive baseline on the same cache machinery (T=1):
    every token is sampled from the warped target logits.

    This is the distribution-equality oracle for ``accept="sample"``
    (DESIGN.md §11): lossless stochastic speculative decoding must produce
    sequences distributed exactly as this loop's.  At
    ``sampling.temperature <= 0`` it is token-identical to ``ar_generate``.
    """
    sp = sampling if sampling is not None else SamplingParams()
    model = get_model(cfg)
    B = tokens.shape[0]
    chain1 = jnp.ones((1, 1), bool)
    depth0 = jnp.zeros((1,), jnp.int32)

    last_hidden, cache = model.prefill(params, cfg, tokens, prompt_lengths,
                                       cache, extra_embeds=extra_embeds)
    base = S.sample(jax.random.fold_in(key, 0),
                    model.unembed(params, cfg, last_hidden),
                    sp.temperature, sp.top_k, sp.top_p)
    out = jnp.zeros((B, max_new), jnp.int32)

    def body(i, c):
        cache, lengths, tok, out = c
        out = out.at[:, i].set(tok)
        hidden, cache = model.decode(params, cfg, cache, tok[:, None], lengths,
                                     chain1, depth0)
        lengths = lengths + 1
        cache = _squeeze_spec(model, cfg, cache, lengths)
        nxt = S.sample(jax.random.fold_in(key, i + 1),
                       model.unembed(params, cfg, hidden[:, 0]),
                       sp.temperature, sp.top_k, sp.top_p)
        return (cache, lengths, nxt, out)

    cache, lengths, tok, out = jax.lax.fori_loop(
        0, max_new, body, (cache, prompt_lengths, base, out))
    return out, lengths


def _squeeze_spec(model, cfg, spec_cache, lengths):
    """Collapse the per-prefix T axis of SSM spec states for T=1 decode.

    Attn entries drop only the in-flight ``*_new`` rows; persistent leaves
    (k/v and, under the int8 cache layout, k_scale/v_scale — DESIGN.md §10)
    pass through untouched, as does the paged layout's ``_pages`` block-
    table state (DESIGN.md §12).  SSM entries additionally drop the
    speculation-root checkpoint leaves (DESIGN.md §17): a T=1 AR step
    always accepts its single token, so the checkpoint is dead here and
    the persistent cache never holds it.
    """
    from repro.models.transformer import PAGES_KEY, SSM_CKPT

    def keep(entry):
        return {n: x for n, x in entry.items() if not n.endswith("_new")}

    def fix_entry(entry):
        if "k" in entry:
            return keep(entry)
        return {k: v[:, :, 0] for k, v in entry.items()
                if not k.endswith(SSM_CKPT)}
    if cfg.family == "encdec":
        out = {"self": keep(spec_cache["self"]), "cross": spec_cache["cross"]}
        if PAGES_KEY in spec_cache:
            out[PAGES_KEY] = spec_cache[PAGES_KEY]
        return out
    return {k: (v if k == PAGES_KEY else fix_entry(v))
            for k, v in spec_cache.items()}
