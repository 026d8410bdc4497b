"""Serving engine v2: static-slot continuous batching over one ``SpecEngine``.

Static-graph discipline (the paper's core constraint) shapes the design:
the decode batch is B fixed slots; every decode step runs all B slots with
per-slot lengths — empty slots carry a dummy row and are masked out of the
commit (``spec_step(..., active=...)``), never out of tensor shapes.

The scheduler is proposer-generic (DESIGN.md §13): it never looks inside
the engine's proposer state — head top-k tensors (Medusa), a draft-model
KV cache, or an n-gram history buffer all thread through admission, the
jitted step and recovery as one opaque pytree, merged per-leaf along the
batch axes the proposer declares (``Proposer.state_axes``), exactly like
the KV cache.  Swapping ``--proposer`` changes zero scheduler code.

Scheduler v2 (DESIGN.md §9) replaces v1's per-request host loops with two
batched device paths:

* **Batched bucketed prefill** — each admission round groups every queued
  request by prompt bucket and prefills a whole bucket group in ONE jitted
  call of shape [n_bucket, bucket] (group sizes padded to powers of two so
  the compile count stays O(log B) per bucket).  The same call merges the
  freshly prefilled cache rows into their slots with a single fused
  gather + select per cache leaf (the ``_update_rows`` idiom: a slot-indexed
  gather from the small group batch plus a ``where`` on the slot mask, which
  the SPMD partitioner keeps local, unlike a scatter).
* **On-device bookkeeping** — per-slot ``n_out``, ``max_new``, ``eos_id``
  and the EOS scan over each step's accepted tokens live inside the jitted
  step; finished slots are masked out of the commit and the host only syncs
  a small per-step verdict struct (``SlotSync``: acc/tokens/done).
  Reaping and slot refill happen in batches on the host side of that sync.

Fault tolerance / straggler mitigation: per-request step budgets and
deadlines; a request that exceeds them is cancelled and its slot freed; a
failed step (injectable for tests) re-queues every in-flight request so a
restarted server loses no work (at-least-once semantics).

``admission="serial"`` keeps the v1 per-request admission path (one
[1, bucket] prefill call plus a host-side cache insert per request) for the
equality tests and the `benchmarks/bench_serving.py` comparison.

Per-request sampling (DESIGN.md §11): each ``Request`` carries
``temperature``/``top_p``, batched as per-slot [B] device arrays through the
jitted step and admission calls and consumed by an ``accept="sample"``
engine's rejection-sampling verification. Temperature 0 warps to exact
greedy, so greedy and sampled requests mix in one static step and a temp-0
request reproduces the greedy scheduler's output token for token.

Cache capacity (DESIGN.md §10): the per-slot device state is dominated by
the attention KV cache, whose storage dtype follows ``cfg.cache_dtype`` —
``init_cache`` builds the int8 layout transparently, and every scheduler
path (batched admission merge, serial insert, recovery rebuild) treats the
cache as an opaque pytree, so quantization needs no scheduler-side code.
Size ``batch_slots`` with ``slots_for_budget``; at a fixed HBM budget the
int8 layout roughly doubles the slots (``benchmarks/bench_kv_quant.py``).

Paged cache + prefix sharing (DESIGN.md §12): under
``cfg.cache_layout == "paged"`` the attention cache is a global block pool
and the *pool* — not the slot count — becomes the admission resource.
Host/device ownership follows §9 exactly:

* **host** — ``BlockPool`` free list + refcounts, per-slot block tables
  (numpy mirror ``_table`` [B, max_blocks], pushed to the device leaf
  ``cache["_pages"]["table"]`` only when dirty), the ``PrefixCache``
  registry, CoW scheduling, admission deferral when an allocation would
  not fit;
* **device** — every read/write through the table inside the same jitted
  step/admission calls as the dense layout (prefill writes land directly
  in the global pool, so the batched-admission cache merge degenerates to
  a passthrough for pool leaves; SSM per-slot leaves still merge by
  src/mask).

Admission reserves a request's worst case (``ceil((prompt + max_new + T +
2)/page_size)`` blocks) up front: exhaustion defers admission (the request
stays queued, FIFO) rather than preempting anything mid-flight — lossless
first.  With ``prefix_cache=True`` a request's prompt blocks are matched
against the registry: shared blocks map into the slot's table refcounted,
a partially matching divergence block is copied on write, and only the
un-cached suffix is prefilled (``SpecEngine.suffix_prefill``).  Reaping a
slot frees its blocks (refcount 0 returns them to the pool) and zeroes its
table row so the slot's dead writes inside the static step sink into the
reserved trash block.

Overload countermeasures (DESIGN.md §14), opted in via ``SchedulerParams``:

* **Chunked prefill** (``chunk_size``) — a prompt longer than the chunk
  runs as successive ``suffix_prefill`` chunks of one fixed [B, chunk]
  shape, all mid-chunk slots advancing together in ONE jitted call per
  scheduler iteration, interleaved with the decode step — so admitting a
  4k-token prompt no longer stalls every decoding slot for a monolithic
  prefill, and per-iteration latency is bounded by B*chunk + one step.
* **Optimistic allocation + preemption** (``preemption``, paged only) —
  admission reserves only ``blocks_for(prompt + T + 2)`` and the decode
  loop grows each slot's table just ahead of its committed length; on
  pool exhaustion the *latest-submitted* running request is preempted:
  blocks freed, proposer-state rows trimmed, request re-queued at the
  head with its delivered tokens folded into the resume prompt, so the
  re-admission is a prefix-cache-assisted recompute that is token-
  identical (temp-0/greedy determinism) to a never-preempted run.
* **Adaptive speculation** (``adaptive_gamma``) — per-slot acceptance is
  tracked as an EMA from the raw per-step verifier acceptance
  (``SlotSync.spec_acc``), and each step the host picks one of a small
  family of PRE-COMPILED step graphs (``SpecEngine.step_dtrees``: chain
  prefixes + the full tree), shrinking speculation when acceptance is low
  — wasted verify FLOPs stop eating decode budget, and no graph is ever
  (re)compiled after warmup.
"""
from __future__ import annotations

import functools
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import SchedulerParams
from repro.core.engine import SpecEngine
from repro.kernels.paging import blocks_for
from repro.models.transformer import PAGES_KEY
from repro.serving.block_pool import BlockPool, PrefixCache

log = logging.getLogger(__name__)

NO_EOS = -1  # device-side "no eos configured" sentinel (token ids are >= 0)


def _merge_rows(big, small, src, mask, axis: int):
    """Gather rows ``src`` of ``small`` into ``big`` where ``mask`` along
    ``axis`` — the scatter-free slot merge (a slot-indexed gather from the
    small group batch plus a ``where`` on the slot mask, which the SPMD
    partitioner keeps local, unlike a scatter).  ``axis`` is the leaf's
    batch axis: 1 for cache leaves ([n_units, B, ...]), proposer-declared
    per state leaf (DESIGN.md §13)."""
    rows = jnp.take(small, src, axis=axis).astype(big.dtype)
    shp = [1] * big.ndim
    shp[axis] = -1
    return jnp.where(mask.reshape(shp), rows, big)


def cache_bytes_per_slot(cfg, max_len: int) -> int:
    """Attention KV-cache bytes one decode slot pins for its lifetime
    (values + int8 scales; SSM state is O(1) in max_len and excluded).

    This is the capacity term of the memory model (DESIGN.md §10): at fixed
    HBM budget the slot count scales inversely with it, so the int8 layout
    (~(D+4)/(2*D) of bf16 bytes) buys ~2x decode slots at the same budget.
    """
    return cfg.kv_cache_bytes_per_token() * max_len


def slots_for_budget(cfg, max_len: int, hbm_bytes: int) -> int:
    """Decode slots a ``hbm_bytes`` cache budget sustains at ``max_len``
    (DESIGN.md §10) — the sizing knob for ``SpecServer(batch_slots=...)``
    under the dense layout, where every slot pins its worst case."""
    return int(hbm_bytes // cache_bytes_per_slot(cfg, max_len))


def blocks_for_budget(cfg, hbm_bytes: int) -> int:
    """Physical pool blocks a ``hbm_bytes`` cache budget sustains — the
    pool-based capacity formula of the paged layout (DESIGN.md §12, §10):
    ``hbm / (kv_cache_bytes_per_token() * page_size)``.  The sizing knob
    for ``SpecServer(n_blocks=...)``; a request then consumes blocks for
    its *own* length (minus any shared prefix) rather than ``max_len``."""
    return int(hbm_bytes // (cfg.kv_cache_bytes_per_token() * cfg.page_size))


@dataclass
class Request:
    """One serving request.  Entirely host-owned: the device never sees a
    Request — admission lowers it into per-slot device arrays (prompt ->
    prefill tokens, max_new/eos_id/temperature/top_p -> slot metadata) and
    ``output`` accumulates from the per-step ``SlotSync``."""
    rid: int
    prompt: np.ndarray                  # [len] int32
    max_new: int
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None  # wall-clock straggler bound
    max_steps: Optional[int] = None     # decode-step budget
    # per-request sampling controls (DESIGN.md §11) — honoured when the
    # engine runs accept="sample"; temperature 0.0 is exact greedy, so a
    # mixed batch of greedy and sampled requests shares one static step
    temperature: float = 0.0
    top_p: float = 1.0
    # encdec only: precomputed frame embeddings [frontend_len, frontend_dim]
    # (the stub encoder input).  Host-retained for the request's lifetime so
    # preemption recovery can re-run the encoder pass (DESIGN.md §17)
    frames: Optional[np.ndarray] = None
    submitted_at: float = field(default_factory=time.monotonic)
    # start of the admission round that first bound it to a slot, and the
    # time its first token was appended (``time.monotonic``, the clock of
    # ``submitted_at``); a resume or a retry moves neither
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    output: List[int] = field(default_factory=list)
    steps: int = 0
    retries: int = 0
    preemptions: int = 0                # times evicted mid-flight (§14)
    status: str = "queued"              # queued|running|done|cancelled|failed


@dataclass
class _Slot:
    request: Optional[Request] = None

    @property
    def free(self):
        return self.request is None


class SlotSync(NamedTuple):
    """The only per-step device->host sync (O(B), computed inside the
    jitted step — DESIGN.md §9).  The host applies it mechanically: append
    ``tokens[i, :acc[i]]`` to slot i's request, reap where ``done``; every
    decision that produced these values (EOS scan, budget clip, masked
    commit) already happened on device."""
    acc: jnp.ndarray        # [B] int32 — tokens to append (EOS/budget-clipped)
    tokens: jnp.ndarray     # [B, K+1] int32 — this step's committed path
    done: jnp.ndarray       # [B] bool — slot finished (EOS hit or budget met)
    spec_acc: jnp.ndarray   # [B] int32 — RAW verifier acceptance (what
                            # ``commit`` advanced the cache length by, pre
                            # EOS/budget clip): feeds the host's committed-
                            # length mirror and the adaptive-speculation
                            # acceptance EMA (DESIGN.md §14)


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: ``jax.jit(named(fn, name))`` compiles the
    program ``jit_<name>``, the name the device trace's ``XLA Modules`` line
    and the host's ``PjitFunction(<name>)`` events carry (a lambda or a
    bound method would show as ``jit__lambda`` or its method name)."""
    program = functools.partial(fn)
    program.__name__ = name
    return program


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class SpecServer:
    """Continuous-batching server over one ``SpecEngine`` (any proposer).

    Host-owned state: the request ``queue``, per-slot ``Request`` bindings
    (``slots``), retry/deadline policy, numpy mirrors of the per-slot step
    inputs (``_active``/``_eos``/``_maxnew``/``_temp``/``_topp``) and —
    under the paged layout — the block allocator and table mirror.
    Device-owned state (donated through every jitted call): ``cache`` (the
    engine cache pytree), ``lengths`` [B] int32, ``base`` [B] int32,
    ``pstate`` (the proposer's opaque state pytree — DESIGN.md §13, merged
    per-leaf along ``Proposer.state_axes``), ``n_out`` [B] int32.  The
    per-step host<->device contract is exactly one ``SlotSync`` down and
    the (dirty) slot metadata up.

    ``proposer_params`` are whatever the engine's proposer consumes:
    Medusa head params, draft-model params, or None for the train-free
    n-gram proposer.

    ``n_blocks`` sizes the paged pool (default: enough for every slot's
    worst case, i.e. dense-equivalent capacity; size from an HBM budget
    with ``blocks_for_budget``).  ``prefix_cache=True`` enables the §12
    shared-prefix registry (paged layout only, attention-only families,
    proposers that can be primed from a prompt suffix).

    ``device`` pins the server's device state and host-driven steps to one
    device (a router replica per chip); ``params`` should already live
    there.  None keeps JAX's default device.
    """

    def __init__(self, engine: SpecEngine, params, proposer_params,
                 batch_slots: int, max_len: int,
                 prompt_buckets=(32, 128, 512), max_retries: int = 1,
                 admission: str = "batched", n_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 sched: Optional[SchedulerParams] = None,
                 device=None):
        assert admission in ("batched", "serial"), admission
        self.engine = engine
        self.device = device
        self.cfg = engine.cfg
        self.model = engine.model
        self.params = params
        self.proposer_params = proposer_params
        self.B = batch_slots
        self.max_len = max_len
        self.sched = sched if sched is not None else SchedulerParams()
        # a bucket wider than the cache cannot be prefilled (the padded
        # [n, bucket] write would overrun [n, max_len] rows) — clamp to
        # max_len so every prompt that fits the cache stays servable;
        # prompts beyond the largest bucket are rejected at admission
        self.buckets = tuple(sorted({min(b, max_len) for b in prompt_buckets}))
        self.max_retries = max_retries
        self.admission = admission

        # paged layout (DESIGN.md §12): the pool is the admission resource
        self.paged = self.cfg.paged
        self.page_size = self.cfg.page_size
        self.blocks_per_slot = blocks_for(max_len, self.page_size)
        if n_blocks is not None and not self.paged:
            raise ValueError("n_blocks requires cache_layout='paged'")
        self.n_blocks = (1 + self.B * self.blocks_per_slot
                         if n_blocks is None else int(n_blocks))
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires cache_layout='paged'")
        if prefix_cache and (self.cfg.num_ssm_layers > 0
                             or self.cfg.family == "encdec"):
            # SSM/hybrid slots now decode and chunk-prefill safely under the
            # checkpointed rollback (DESIGN.md §17), but prefix-cache
            # admission *skips* prefill for matched tokens — a shared KV
            # block carries no recurrent/cross state to restore from, so a
            # cache hit would leave the slot's SSM (or encoder) state cold.
            raise ValueError(
                "prefix_cache shares KV blocks only; SSM/encdec state "
                "cannot be reconstructed from them (DESIGN.md §17 — use "
                "chunked prefill / preemption for these families)")
        if prefix_cache and not engine.proposer.supports_prefix:
            raise ValueError(
                f"prefix_cache needs a proposer that can be primed from a "
                f"prompt suffix; {type(engine.proposer).__name__} cannot "
                "(DESIGN.md §13)")
        self.prefix_enabled = prefix_cache

        # overload countermeasures (DESIGN.md §14)
        self.chunk = min(int(self.sched.chunk_size), max_len) \
            if self.sched.chunk_size else 0
        if self.chunk and not engine.proposer.supports_prefix:
            raise ValueError(
                f"chunked prefill rides the suffix_prefill path; "
                f"{type(engine.proposer).__name__} cannot be primed from a "
                "suffix (DESIGN.md §13)")
        if self.chunk and self.cfg.family == "encdec":
            # SSM/hybrid families are chunk-safe since the checkpointed
            # rollback (DESIGN.md §17): commit restores the speculation-root
            # state on every masked row, so interleaving chunks with live
            # decode slots can no longer corrupt recurrent state.  Encdec
            # stays refused: its cross-attn cache comes from the encoder
            # pass inside whole-prompt prefill, which cannot be chunked.
            raise ValueError(
                "chunked prefill cannot split an encoder-decoder prompt: "
                "the cross-attention cache is built by the encoder pass "
                "inside whole-prompt prefill (DESIGN.md §17)")
        self.preemption = bool(self.sched.preemption)
        if self.preemption and not self.paged:
            raise ValueError("preemption (optimistic block allocation) "
                             "requires cache_layout='paged' — the dense "
                             "layout has no pool to exhaust (DESIGN.md §14)")
        self.adaptive = bool(self.sched.adaptive_gamma)

        self.queue: deque[Request] = deque()
        self.slots = [_Slot() for _ in range(self.B)]
        self.done: Dict[int, Request] = {}
        self._rid = 0

        # adaptive-speculation graph family (DESIGN.md §14): one jitted
        # step per level, compiled lazily on first use, selected host-side
        self._levels = (engine.step_dtrees(self.sched.gamma_levels)
                        if self.adaptive else [(engine.dtree.K, engine.dtree)])
        self._level = len(self._levels) - 1   # start at full speculation
        self._head_passes = [engine.lm_head_passes(self.B, dt)
                             for _, dt in self._levels]
        self.stats = self._fresh_stats()

        with jax.default_device(self.device):
            self._reset_device_state()
            self._key = jax.random.PRNGKey(0)

        # host mirrors of the per-slot device bookkeeping inputs
        self._active = np.zeros((self.B,), bool)
        self._eos = np.full((self.B,), NO_EOS, np.int32)
        self._maxnew = np.zeros((self.B,), np.int32)
        self._temp = np.zeros((self.B,), np.float32)   # per-request sampling
        self._topp = np.ones((self.B,), np.float32)    # (DESIGN.md §11)
        self._done_now = np.zeros((self.B,), bool)
        self._slotmeta_dev = None   # device copies, refreshed only on mutation
        # §14 host bookkeeping: committed-cache-length mirror (tracks the
        # raw SlotSync.spec_acc, which is what commit advanced by), the
        # per-slot acceptance EMA, and the mid-chunk prefill cursors
        self._len_host = np.zeros((self.B,), np.int64)
        self._acc_ema = np.ones((self.B,), np.float64)
        self._chunk_state: Dict[int, dict] = {}

        # one jitted callable each; XLA re-specialises per input shape, so the
        # [n_group, bucket] admission variants share a single cache here.
        # The B-slot cache/state args are donated: the old buffers are dead
        # after each call, so XLA aliases them instead of holding 2x cache.
        # Each program carries a stable name (``named``) for the trace.
        self._admit_jit = jax.jit(
            named(self._admit_paged_impl if self.paged
                  else self._admit_bucket_impl, "admit_group"),
            donate_argnums=(7, 8, 9, 10, 11))  # speclint: donates=cache,lengths,base,pstate,n_out
        self._prefill_jit = jax.jit(named(
            lambda p, pp, t, l, c, key, temp, topp, st, fr=None:
                self.engine.prefill(
                    p, pp, t, l, c, extra_embeds=fr, key=key,
                    temperature=temp, top_p=topp, state=st), "prefill_one"))
        self._step_jit = jax.jit(named(self._serve_step_impl, "serve_step"),
                                 donate_argnums=(2, 3, 4, 5, 6))  # speclint: donates=cache,lengths,base,pstate,n_out
        # per-level step graphs (the full-tree level deliberately does NOT
        # alias self._step_jit: tests monkeypatch _step_jit to inject
        # failures, and that must keep working for the default path)
        self._step_jits = [
            jax.jit(named(functools.partial(self._serve_step_impl, dtree=dt),
                          "serve_step"),
                    donate_argnums=(2, 3, 4, 5, 6))  # speclint: donates=cache,lengths,base,pstate,n_out
            for _, dt in self._levels]
        self._trim_jit = jax.jit(named(
            lambda st, keep: self.engine.proposer.reset_rows(st, keep),
            "trim_proposer"),
            donate_argnums=(0,))  # speclint: donates=st
        if self.paged or self.chunk:
            self._suffix_jit = jax.jit(named(self._suffix_impl,
                                             "suffix_prefill"),
                                       donate_argnums=(6, 7, 8, 9, 10))  # speclint: donates=cache,lengths,base,pstate,n_out
        if self.paged:
            self._copy_jit = jax.jit(named(self._copy_blocks_impl,
                                           "copy_blocks"),
                                     donate_argnums=(0,))  # speclint: donates=cache
        if getattr(self.engine.proposer, "primes_from_tokens", False):
            self._prime_tokens_jit = jax.jit(named(
                lambda st, toks, tl, base, mask:
                    self.engine.proposer.prime_tokens(st, toks, tl, base,
                                                      mask), "prime_tokens"),
                donate_argnums=(0,))  # speclint: donates=st

    def _fresh_stats(self) -> dict:
        return {"prefill_calls": 0, "admitted": 0, "steps": 0,
                "deferred": 0, "prefill_tokens": 0, "cached_tokens": 0,
                "cow_copies": 0, "peak_blocks": 0,
                # §14 overload counters
                "chunk_calls": 0, "preemptions": 0, "resumed": 0,
                "reclaimed_blocks": 0, "grown_blocks": 0,
                "gamma_steps": {g: 0 for g, _ in self._levels},
                # §17 rollback counter: slot-steps whose SSM recurrent state
                # was restored from the speculation-root checkpoint (masked
                # rows of a step/chunk call; 0 for attention-only families)
                "ssm_restores": 0,
                # iterations whose admit/decode raised and were recovered
                # (device faults included — XLA runtime errors, OOM)
                "step_failures": 0,
                # active slots summed over decode steps (counted at
                # dispatch), and the verifier's raw acceptance
                # (SlotSync.spec_acc, before EOS/budget clipping) summed
                # over them: accepted_tokens / slot_steps is the accepted
                # length per slot-step
                "slot_steps": 0, "accepted_tokens": 0,
                # passes over the lm head of the fused verify kernel summed
                # over decode steps (SpecEngine.lm_head_passes; 0 without
                # verify fusion): one a step when every slot shares a pass
                "lm_head_passes": 0}

    # ------------------------------------------------------------------ API

    def submit(self, prompt: np.ndarray, max_new: int, eos_id=None,
               deadline_s=None, max_steps=None, temperature: Optional[float] = None,
               top_p: Optional[float] = None, extra_embeds=None) -> int:
        """``temperature``/``top_p`` take effect when the engine verifies
        with ``accept="sample"`` (DESIGN.md §11); omitted values fall back
        to the engine's ``SamplingParams``, and temperature 0.0 reproduces
        greedy output exactly.  Greedy/typical engines ignore them.

        ``extra_embeds`` [frontend_len, frontend_dim] is required for the
        encdec family (the stub encoder's frame embeddings, DESIGN.md §17)
        and rejected for every other family — decoder-only frontends fold
        their prefix at prefill and are not per-request state here."""
        sp = self.engine.sampling
        if self.cfg.family == "encdec":
            if extra_embeds is None:
                raise ValueError(
                    "encdec requests need extra_embeds [frontend_len, "
                    "frontend_dim]: the encoder pass runs at admission "
                    "(DESIGN.md §17)")
            extra_embeds = np.asarray(extra_embeds, np.float32)
            want = (self.cfg.frontend_len,
                    self.cfg.frontend_dim or self.cfg.d_model)
            if extra_embeds.shape != want:
                raise ValueError(
                    f"extra_embeds shape {extra_embeds.shape} != {want}")
        elif extra_embeds is not None:
            raise ValueError(
                f"extra_embeds is encdec-only; {self.cfg.family!r} requests "
                "carry tokens alone")
        if (getattr(self.engine, "verify_fusion", False)
                and self.engine.accept == "sample"
                and top_p is not None and top_p != 1.0):
            # the fused epilogue keeps only Verdict-sized statistics; a
            # top-p warp needs the sorted full row (DESIGN.md §15)
            raise ValueError("verify_fusion rejects per-request top_p != 1.0")
        self._rid += 1
        self.queue.append(Request(
            self._rid, np.asarray(prompt, np.int32), max_new, eos_id,
            deadline_s, max_steps or 4 * max_new,
            temperature=sp.temperature if temperature is None else temperature,
            top_p=sp.top_p if top_p is None else top_p,
            frames=extra_embeds))
        return self._rid

    def result(self, rid: int) -> Optional[Request]:
        return self.done.get(rid)

    @property
    def busy(self) -> bool:
        """True while any work is queued or in flight."""
        return bool(self.queue) or any(not s.free for s in self.slots)

    def step_once(self, fail_hook: Optional[Callable[[int], bool]] = None,
                  it: int = 0):
        """One scheduler iteration: batched admit -> decode step -> batched
        reap. ``fail_hook(it)`` returning True simulates a step failure.

        Admission sits inside the recovery scope: its jitted call donates the
        slot state too, so a failure there must re-queue and rebuild exactly
        like a failed decode step (requests attach to slots before prefill,
        so ``_recover`` sees them).  So do the chunk advance and the decode
        step — mid-chunk slots re-queue like any in-flight request
        (DESIGN.md §14).  Every recovered failure is logged and counted in
        ``stats["step_failures"]``: XLA's runtime and out-of-memory errors
        are RuntimeErrors too, and must not pass for a clean run.

        Under the profiler the iteration is a ``serve.step`` span holding
        ``serve.admit``, ``serve.chunk``, ``serve.decode`` (dispatch),
        ``serve.sync`` (the host blocked on the device), ``serve.apply``,
        ``serve.reap`` and ``serve.recover``; off, each costs about a
        microsecond and formats nothing."""
        with jax.default_device(self.device), TraceAnnotation("serve.step"):
            try:
                self._admit()
                if fail_hook is not None and fail_hook(it):
                    raise RuntimeError("injected step failure")
                if self._chunk_state:
                    with TraceAnnotation("serve.chunk"):
                        self._chunk_step()
                self._decode_step()
            except RuntimeError as e:
                self.stats["step_failures"] += 1
                log.warning("scheduler iteration %d failed, re-queueing "
                            "in-flight requests: %s: %s", it,
                            type(e).__name__, e)
                with TraceAnnotation("serve.recover"):
                    self._recover()
            self._reap()

    def run(self, max_iters: int = 10_000,
            fail_hook: Optional[Callable[[int], bool]] = None):
        """Drive until all work is done."""
        it = 0
        while self.busy and it < max_iters:
            self.step_once(fail_hook, it)
            it += 1
        return it

    def release_all(self):
        """Cancel and resolve every queued and in-flight request (benchmark/
        test helper; device state is dead until the slots are re-admitted)."""
        for req in list(self.queue):
            req.status = "cancelled"
            self.done[req.rid] = req
        self.queue.clear()
        for i, slot in enumerate(self.slots):
            if slot.request is not None:
                slot.request.status = "cancelled"
                self.done[slot.request.rid] = slot.request
                slot.request = None
            if self.paged:
                self.pool.free(self._slot_alloc.pop(i, []))
                self._table[i, :] = 0
                self._matched[i] = 0
        if self.paged:
            self._table_dirty = True
        self._reset_host_slots()

    def reset(self):
        """Fresh server, warm graphs: drop every queued / in-flight /
        finished request, zero the stats and rebuild the device state while
        keeping all compiled step/admission callables — so a test or bench
        harness can run many independent scenarios on one ``SpecServer``
        without paying recompilation per scenario."""
        self.queue.clear()
        self.done.clear()
        for slot in self.slots:
            slot.request = None
        self.stats = self._fresh_stats()
        self._level = len(self._levels) - 1
        with jax.default_device(self.device):
            self._reset_device_state()
        self._reset_host_slots()

    def _reset_host_slots(self):
        """Clear every host per-slot mirror to the no-tenant state."""
        self._active[:] = False
        self._done_now[:] = False
        self._len_host[:] = 0
        self._acc_ema[:] = 1.0
        self._chunk_state.clear()
        self._slotmeta_dev = None

    # ---------------------------------------------------- jitted device code

    def _admit_bucket_impl(self, params, proposer_params, toks, plens, gtemp,
                           gtopp, key, cache, lengths, base, pstate,
                           n_out, src, mask, frames=None):
        """Prefill one bucket group [n, bucket] and merge it into the B-slot
        state in the same compiled call.

        src [B] int32: for each slot, its row in the group (garbage where
        mask is False); mask [B] bool: slot receives a new request.  The
        merge is a gather from the small group batch + elementwise select —
        the scatter-free formulation ``_update_rows`` uses, which keeps a
        seq-sharded cache local under SPMD; proposer-state leaves merge the
        same way along their declared batch axes (DESIGN.md §13).
        gtemp/gtopp [n] are the group rows' sampling params (the base token
        of a sample-mode engine is drawn per request at its own temperature
        — DESIGN.md §11).
        """
        n = toks.shape[0]
        cache_n = self.engine.init_cache(n, self.max_len)
        st_n = self.engine.init_proposer_state(n, self.max_len)
        cache_n, len_n, base_n, st_n = self.engine.prefill(
            params, proposer_params, toks, plens, cache_n,
            extra_embeds=frames, key=key, temperature=gtemp, top_p=gtopp,
            state=st_n)
        srcc = jnp.clip(src, 0, n - 1)
        # safe per-slot merge: this impl is selected only when the cache is
        # dense ([units, B, S, ...] leaves, slot axis 1 everywhere); the
        # paged layout admits through _admit_paged_impl, which splits pool
        # leaves before merging
        cache = jax.tree.map(  # speclint: disable=pytree-axis
            lambda b, s: _merge_rows(b, s, srcc, mask, 1), cache, cache_n)
        pstate = jax.tree.map(
            lambda b, s, ax: _merge_rows(b, s, srcc, mask, ax),
            pstate, st_n, self._sax)
        lengths = jnp.where(mask, len_n[srcc], lengths)
        base = jnp.where(mask, base_n[srcc], base)
        n_out = jnp.where(mask, 0, n_out)
        return cache, lengths, base, pstate, n_out

    def _admit_paged_impl(self, params, proposer_params, toks, plens, gtemp,
                          gtopp, key, cache, lengths, base, pstate,
                          n_out, src, mask, gtable, frames=None):
        """Paged variant of ``_admit_bucket_impl`` (DESIGN.md §12).

        Prefill writes land in the *global* pool through ``gtable``
        [n, max_blocks] (the admitted slots' table rows; padding rows are
        all-zero so their writes sink into the trash block), so the cache
        merge disappears for pool leaves — only per-slot leaves (SSM
        recurrent state; the encdec cross-attn cache, which has k/v but is
        [nu, B, ...] dense — DESIGN.md §17), the [B]-sized step state and
        the proposer state still merge by ``src``/``mask``.
        """
        n = toks.shape[0]

        def per_slot(pos, entry):
            return pos == "cross" or "k" not in entry
        view = {}
        for pos, entry in cache.items():
            if pos == PAGES_KEY:
                continue
            if per_slot(pos, entry):            # per-slot state: fresh rows
                view[pos] = {nm: jnp.zeros((x.shape[0], n) + x.shape[2:],
                                           x.dtype) for nm, x in entry.items()}
            else:
                view[pos] = entry               # global pool leaves, shared
        view[PAGES_KEY] = {"table": gtable}
        st_n = self.engine.init_proposer_state(n, self.max_len)
        view, len_n, base_n, st_n = self.engine.prefill(
            params, proposer_params, toks, plens, view,
            extra_embeds=frames, key=key, temperature=gtemp, top_p=gtopp,
            state=st_n)
        srcc = jnp.clip(src, 0, n - 1)

        new_cache = {}
        for pos, entry in cache.items():
            if pos == PAGES_KEY:
                new_cache[pos] = entry          # B-slot table: host-managed
            elif per_slot(pos, entry):
                new_cache[pos] = jax.tree.map(
                    lambda b, s: _merge_rows(b, s, srcc, mask, 1),
                    entry, view[pos])
            else:
                new_cache[pos] = view[pos]      # pool updated in place
        pstate = jax.tree.map(
            lambda b, s, ax: _merge_rows(b, s, srcc, mask, ax),
            pstate, st_n, self._sax)
        lengths = jnp.where(mask, len_n[srcc], lengths)
        base = jnp.where(mask, base_n[srcc], base)
        n_out = jnp.where(mask, 0, n_out)
        return new_cache, lengths, base, pstate, n_out

    def _suffix_impl(self, params, proposer_params, stoks, nv, mlen, key,
                     cache, lengths, base, pstate, n_out, smask,
                     temp, topp):
        """Prefix-cache admission forward (DESIGN.md §12): continue prefill
        from cached prefix rows for the slots in ``smask`` [B] bool.

        stoks [B, T_bucket] right-padded suffix tokens (garbage on inactive
        rows), nv [B] true suffix lengths (1 on inactive rows), mlen [B]
        cached-prefix length.  All B slots run the same causal decode, but
        only ``smask`` rows merge their new base/head state.

        Dead-write hazard (unique to this call): another slot admitted in
        the *same* round already has its new block table installed but not
        yet its device length, so letting it write at its stale length
        would corrupt the shared prefix blocks its table now maps.  Every
        non-``smask`` slot therefore runs this call at length = capacity —
        its dead writes fall past the table's reach and sink into the
        trash block (kernels/paging.py) — and has its real length restored
        on return.  Chunked prefill (DESIGN.md §14) reuses this same call
        under the DENSE layout too, where capacity is ``max_len`` and the
        out-of-range writes are dropped by ``_update_rows``'s bounds check
        instead of a trash block.
        """
        cap = jnp.int32(self.blocks_per_slot * self.page_size
                        if self.paged else self.max_len)
        lens_in = jnp.where(smask, mlen, cap)
        st_n = self.engine.init_proposer_state(self.B, self.max_len)
        cache, lens_new, base_n, st_n = self.engine.suffix_prefill(
            params, proposer_params, cache, lens_in, stoks, nv, smask,
            key=key, temperature=temp, top_p=topp, state=st_n)
        rows = jnp.arange(self.B)
        lengths = jnp.where(smask, lens_new, lengths)
        base = jnp.where(smask, base_n, base)
        pstate = jax.tree.map(
            lambda b, s, ax: _merge_rows(b, s, rows, smask, ax),
            pstate, st_n, self._sax)
        n_out = jnp.where(smask, 0, n_out)
        return cache, lengths, base, pstate, n_out

    def _copy_blocks_impl(self, cache, src, dst):
        """Copy-on-write device op: pool rows of physical blocks ``src``
        [m] copy into blocks ``dst`` [m] across every attention pool leaf
        (values and int8 scales; one shared block id space — DESIGN.md
        §12).  Padding pairs are (0, 0): a trash-to-trash no-op.  The
        encdec ``cross`` entry has k/v but is per-slot dense, not pool-form
        — block ids never index it (DESIGN.md §17)."""
        def cp(x):
            return x.at[:, dst].set(x[:, src])
        new = {}
        for pos, entry in cache.items():
            if pos != PAGES_KEY and pos != "cross" and "k" in entry:
                new[pos] = {nm: (cp(x) if nm in ("k", "v", "k_scale",
                                                 "v_scale") else x)
                            for nm, x in entry.items()}
            else:
                new[pos] = entry
        return new

    def _serve_step_impl(self, params, proposer_params, cache, lengths, base,
                         pstate, n_out, key, active, eos_id, max_new,
                         temp, topp, dtree=None):
        """One masked speculative step + on-device bookkeeping.

        EOS detection, budget clipping and the done mask are folded into the
        compiled step so the host only reads the small ``SlotSync`` struct.
        ``temp``/``topp`` [B] are the per-request sampling params batched as
        per-slot device arrays (consumed by accept="sample" verification).
        ``dtree`` selects a member of the adaptive-speculation graph family
        (DESIGN.md §14) — each member is its own compiled graph, closed
        over its topology, so selection is a host-side list index.
        """
        cache, lengths, verdict, pstate = self.engine.spec_step(
            params, proposer_params, cache, lengths, base, pstate, key,
            active=active, temperature=temp, top_p=topp, dtree=dtree)
        K1 = verdict.path_tokens.shape[1]
        pos = jnp.arange(K1)
        within = pos[None, :] < verdict.acc[:, None]
        is_eos = (within & (verdict.path_tokens == eos_id[:, None])
                  & (eos_id != NO_EOS)[:, None])
        has_eos = jnp.any(is_eos, axis=1)
        eos_pos = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
        n_take = jnp.where(has_eos, eos_pos + 1, verdict.acc)
        n_take = jnp.minimum(n_take, jnp.maximum(max_new - n_out, 0))
        n_take = jnp.where(active, n_take, 0)
        n_out = n_out + n_take
        done = active & ((n_out >= max_new) | has_eos)
        sync = SlotSync(n_take, verdict.path_tokens, done,
                        jnp.where(active, verdict.acc, 0))
        return cache, lengths, verdict.next_token, pstate, n_out, sync

    # ------------------------------------------------------------- internals

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _effective(self, req: Request):
        """(effective prompt, remaining max_new) for (re-)admission.

        A preempted request resumes by folding its already-delivered tokens
        into the prompt (DESIGN.md §14): the re-admission recomputes (or
        prefix-matches) exactly the sequence the first tenure committed, so
        at temperature 0 the resumed continuation is token-identical to a
        never-preempted run."""
        if req.output:
            return (np.concatenate([req.prompt,
                                    np.asarray(req.output, np.int32)]),
                    req.max_new - len(req.output))
        return req.prompt, req.max_new

    def _admit(self):
        """One admission round (``_admit_round``) under a ``serve.admit``
        span that carries the requests admitted and the first rid."""
        with TraceAnnotation("serve.admit") as span:
            took = self._admit_round(time.monotonic())
            if took:
                span.set_metadata(admitted=len(took), rid=took[0].rid)

    def _admit_round(self, now: float) -> List[Request]:
        """Admission round (host): drain the queue into free slots.

        Dense: the free-slot count is the only resource.  Paged (DESIGN.md
        §12): each request must also reserve its block count from the pool
        — worst case (``prompt + max_new + T + 2`` tokens) by default,
        optimistic (``prompt + T + 2``, grown on demand by
        ``_ensure_blocks``) under ``sched.preemption`` (DESIGN.md §14).
        ``_plan_blocks`` returning None defers the request (queue head,
        FIFO preserved) until a reap frees blocks.  Prefix-cached requests
        (a non-empty match) admit via the per-request suffix path; prompts
        longer than ``sched.chunk_size`` only install their slot here and
        stream through ``_chunk_step``; the rest go through the bucketed
        group prefill, whose writes land directly in the global pool
        through the group's table rows.

        ``now`` stamps ``admitted_at`` on first binding; returns the requests
        bound to slots this round."""
        free = [i for i, s in enumerate(self.slots) if s.free]
        take: List[tuple] = []
        while self.queue and len(take) < len(free):
            req = self.queue.popleft()
            p_ext, mn = self._effective(req)
            # reject what cannot run losslessly: prompts that don't fit the
            # cache budget, or (chunking off) exceed the largest prefill
            # bucket (prefill would silently truncate the prompt but keep
            # the full length).  Under optimistic allocation also reject a
            # request whose worst case exceeds the whole pool: admitting it
            # would guarantee an unservable growth demand later (preempting
            # everything else could still not fit it).
            if (len(p_ext) + mn + self.engine.dtree.T + 2 > self.max_len
                    or (not self.chunk and len(p_ext) > self.buckets[-1])
                    or (self.paged and self.preemption and
                        blocks_for(len(p_ext) + mn + self.engine.dtree.T + 2,
                                   self.page_size) > self.n_blocks - 1)):
                req.status = "failed"
                self.done[req.rid] = req
                continue
            plan = self._plan_blocks(req, p_ext, mn) if self.paged else None
            if self.paged and plan is None:
                # pool exhausted: defer — re-queue at the head and stop
                # admitting so order is preserved; nothing mid-flight is
                # touched here (under §14 preemption the *decode* path may
                # still evict to make room for already-admitted slots)
                self.queue.appendleft(req)
                self.stats["deferred"] += 1
                break
            take.append((req, plan, p_ext, mn))
        if not take:
            return []
        pairs = []          # (slot, req, p_ext) for this round's prefills
        cows = []
        for i, (req, plan, p_ext, mn) in zip(free, take):
            req.status = "running"
            if req.admitted_at is None:
                req.admitted_at = now
            self.slots[i].request = req
            if req.output:
                self.stats["resumed"] += 1
            self._eos[i] = NO_EOS if req.eos_id is None else req.eos_id
            self._maxnew[i] = mn
            self._temp[i] = req.temperature
            self._topp[i] = req.top_p
            self._acc_ema[i] = 1.0
            matched = 0
            if plan is not None:
                row = plan["shared"] + plan["fresh"]
                self._table[i, :] = 0
                self._table[i, : len(row)] = row
                self._table_dirty = True
                self._slot_alloc[i] = row
                self._matched[i] = plan["matched"]
                matched = plan["matched"]
                if plan["cow"] is not None:
                    cows.append((plan["cow"], plan["fresh"][0]))
            if self.chunk and len(p_ext) - matched > self.chunk:
                # chunked prefill (DESIGN.md §14): the slot holds its
                # request but stays inactive; _chunk_step streams the
                # prompt through suffix_prefill, one chunk per iteration
                self._chunk_state[i] = {"toks": p_ext, "pos": matched}
                self._active[i] = False
                self._len_host[i] = matched
            else:
                self._active[i] = True
                self._len_host[i] = len(p_ext)
                pairs.append((i, req, p_ext))
        self._slotmeta_dev = None
        self.stats["admitted"] += len(take)
        if self.paged:
            self._admit_paged(pairs, cows)
        elif self.admission == "serial":
            for i, req, p_ext in pairs:
                self._prefill_one(req, i, p_ext)
        else:
            self._admit_batched(pairs)
        return [req for req, *_ in take]

    # ---- paged admission (host side, DESIGN.md §12) -----------------------

    def _plan_blocks(self, req: Request, p_ext: np.ndarray, mn: int):
        """Reserve blocks for ``req`` (all-or-nothing; None = defer).

        ``p_ext``/``mn`` are the request's effective prompt and remaining
        budget (``_effective`` — a resumed request's prompt includes its
        already-delivered tokens).  The default reservation is the worst
        case (``p_ext + mn + T + 2`` tokens); under ``sched.preemption``
        it is optimistic — just the prompt plus one step of speculation
        slack (``p_ext + T + 2``), with ``_ensure_blocks`` growing the
        slot's table ahead of the committed length every decode step
        (DESIGN.md §14).

        Returns {"shared": [ids], "fresh": [ids], "matched": int,
        "cow": src_block|None}.  ``shared`` blocks hold an already-cached
        prompt prefix (refcount bumped); ``fresh`` blocks are newly owned;
        ``matched`` counts cached prompt tokens (suffix starts there).  A
        partial divergence-block match sets ``cow``: the donor block to
        copy into ``fresh[0]`` before the suffix prefill overwrites rows
        [matched % page_size, ...) of the copy — the cow source is pinned
        (one extra refcount) until ``_admit_paged`` has issued the copy.

        Ordering matters: the matched blocks (shared + cow source) are
        pinned *before* eviction/allocation runs, so a registry-only
        matched block can neither be evicted nor handed back by ``alloc``
        as one of this request's own fresh blocks."""
        shared, div_block, div_tokens = [], None, 0
        if self.prefix is not None:
            shared, div_block, div_tokens = self.prefix.match(p_ext)
        pinned = shared + ([div_block] if div_tokens else [])
        self.pool.share(pinned)
        need_tokens = len(p_ext) + self.engine.dtree.T + 2
        if not self.preemption:
            need_tokens += mn           # worst-case reservation (§12)
        total = blocks_for(need_tokens, self.page_size)
        n_fresh = total - len(shared)
        shortfall = n_fresh - self.pool.available
        if shortfall > 0 and self.prefix is not None:
            self.prefix.evict(self.pool, shortfall)   # all-or-nothing
        fresh = self.pool.alloc(n_fresh)
        if fresh is None:
            self.pool.free(pinned)                    # undo the pins
            if pinned:
                # fall back to a no-sharing plan: with the match unpinned,
                # eviction may reclaim those very blocks — a full prefill
                # beats deferring forever when the only reclaimable space
                # IS the matched prefix
                shortfall = total - self.pool.available
                if shortfall > 0:
                    self.prefix.evict(self.pool, shortfall)
                fresh = self.pool.alloc(total)
                if fresh is not None:
                    return {"shared": [], "fresh": fresh, "matched": 0,
                            "cow": None}
            return None
        matched = len(shared) * self.page_size + div_tokens
        return {"shared": shared, "fresh": fresh, "matched": matched,
                "cow": div_block if div_tokens else None}

    def _admit_paged(self, pairs, cows):
        """Execute a planned paged admission round: push tables, run CoW
        copies, group-prefill unmatched requests, suffix-prefill matched
        ones, then register the new prompts in the prefix cache.  Chunked
        slots are absent from ``pairs`` — their table rows and CoW copies
        are installed here, but their prefill streams via ``_chunk_step``
        (registration happens when the last chunk lands)."""
        self._push_table()
        if cows:
            n = _pow2(len(cows))
            src = np.zeros((n,), np.int32)
            dst = np.zeros((n,), np.int32)     # pad pairs: trash -> trash
            for j, (s, d) in enumerate(cows):
                src[j], dst[j] = s, d
            self.cache = self._copy_jit(self.cache, jnp.asarray(src),
                                        jnp.asarray(dst))
            self.pool.free([s for s, _ in cows])   # release the cow pins
            self.stats["cow_copies"] += len(cows)
        full = [p for p in pairs if self._matched[p[0]] == 0]
        pref = [p for p in pairs if self._matched[p[0]] > 0]
        if self.admission == "serial":
            for pair in full:
                self._admit_batched([pair])
        elif full:
            self._admit_batched(full)
        for i, req, p_ext in pref:
            self._admit_suffix_one(i, p_ext, self._matched[i])
        for i, req, p_ext in pairs:
            self.stats["prefill_tokens"] += len(p_ext) - self._matched[i]
            self.stats["cached_tokens"] += self._matched[i]
            if self.prefix is not None:
                self.prefix.register(p_ext, self._table[i], self.pool)
        self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                        self.pool.in_use)

    def _admit_suffix_one(self, slot_idx: int, p_ext: np.ndarray, matched: int):
        """Admit one prefix-matched request: causal suffix prefill over the
        slot's (already mapped) cached prefix (``SpecEngine.suffix_prefill``
        via ``_suffix_impl``).  One [B, suffix_bucket] call per request —
        prefix admission trades the dense path's group batching for block
        reuse; the prefill-token savings dominate when prefixes are long."""
        suffix = p_ext[matched:]
        bucket = self._bucket(len(suffix))
        stoks = np.zeros((self.B, bucket), np.int32)
        stoks[slot_idx, : len(suffix)] = suffix[:bucket]
        nv = np.ones((self.B,), np.int32)
        nv[slot_idx] = len(suffix)
        mlen = np.zeros((self.B,), np.int32)
        mlen[slot_idx] = matched
        smask = np.zeros((self.B,), bool)
        smask[slot_idx] = True
        self._key, sub = jax.random.split(self._key)
        (self.cache, self.lengths, self.base, self.pstate,
         self.n_out) = self._suffix_jit(
            self.params, self.proposer_params, jnp.asarray(stoks),
            jnp.asarray(nv), jnp.asarray(mlen), sub, self.cache,
            self.lengths, self.base, self.pstate, self.n_out,
            jnp.asarray(smask), jnp.asarray(self._temp),
            jnp.asarray(self._topp))
        if getattr(self.engine.proposer, "primes_from_tokens", False):
            self._prime_full_history(slot_idx, p_ext)
        self.stats["prefill_calls"] += 1

    def _prime_full_history(self, slot_idx: int, p_ext: np.ndarray):
        """Re-prime a token-lookup proposer with the FULL prompt after a
        prefix-cache suffix admission.

        ``_suffix_impl`` primes the proposer from the un-cached suffix
        only (the target never re-reads cached prompt rows), which leaves
        an n-gram history cold exactly where prefix sharing makes repeats
        most likely.  The host still knows the complete token ids, so
        proposers declaring ``primes_from_tokens`` get one extra jitted
        pass rebuilding this slot's history — bucketed like admission, and
        prompts past the largest bucket keep their most recent window.
        Identity-safe: proposals only ever change speculation hit rate,
        never the verified output (DESIGN.md §12/§13)."""
        W = self._bucket(min(len(p_ext), self.buckets[-1]))
        window = p_ext[-W:] if len(p_ext) > W else p_ext
        ptoks = np.zeros((self.B, W), np.int32)
        ptoks[slot_idx, : len(window)] = window
        tl = np.ones((self.B,), np.int32)
        tl[slot_idx] = len(window)
        pmask = np.zeros((self.B,), bool)
        pmask[slot_idx] = True
        self.pstate = self._prime_tokens_jit(
            self.pstate, jnp.asarray(ptoks), jnp.asarray(tl), self.base,
            jnp.asarray(pmask))

    def _admit_batched(self, pairs):
        """Group the admitted requests by prompt bucket and prefill each
        group in one jitted call (host builds the [n, bucket] numpy inputs;
        device does everything else).  Under the paged layout the group's
        table rows ride along (``gtable`` [n, max_blocks]; padding rows
        all-zero = trash-sinked writes) and the call is the paged variant."""
        groups: Dict[int, list] = {}
        for i, req, p_ext in pairs:
            groups.setdefault(self._bucket(len(p_ext)), []).append(
                (i, req, p_ext))
        for bucket, grp in groups.items():
            n = _pow2(len(grp))
            toks = np.zeros((n, bucket), np.int32)
            plens = np.ones((n,), np.int32)      # padding rows: dummy length-1
            gtemp = np.zeros((n,), np.float32)
            gtopp = np.ones((n,), np.float32)
            src = np.zeros((self.B,), np.int32)
            mask = np.zeros((self.B,), bool)
            gtable = (np.zeros((n, self.blocks_per_slot), np.int32)
                      if self.paged else None)
            encdec = self.cfg.family == "encdec"
            gframes = (np.zeros((n, self.cfg.frontend_len,
                                 self.cfg.frontend_dim or self.cfg.d_model),
                                np.float32) if encdec else None)
            for j, (i, req, p_ext) in enumerate(grp):
                toks[j, : len(p_ext)] = p_ext[:bucket]
                plens[j] = len(p_ext)
                gtemp[j] = req.temperature
                gtopp[j] = req.top_p
                src[i] = j
                mask[i] = True
                if self.paged:
                    gtable[j] = self._table[i]
                if encdec:
                    gframes[j] = req.frames
            self._key, sub = jax.random.split(self._key)
            extra = (jnp.asarray(gtable),) if self.paged else ()
            if encdec:
                extra += (jnp.asarray(gframes),)
            (self.cache, self.lengths, self.base, self.pstate,
             self.n_out) = self._admit_jit(
                self.params, self.proposer_params, jnp.asarray(toks),
                jnp.asarray(plens), jnp.asarray(gtemp), jnp.asarray(gtopp),
                sub, self.cache, self.lengths, self.base, self.pstate,
                self.n_out, jnp.asarray(src), jnp.asarray(mask),
                *extra)
            self.stats["prefill_calls"] += 1

    def _prefill_one(self, req: Request, slot_idx: int,
                     p_ext: Optional[np.ndarray] = None):
        """v1 serial admission: one [1, bucket] prefill + host-side insert."""
        p_ext = req.prompt if p_ext is None else p_ext
        bucket = self._bucket(len(p_ext))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : len(p_ext)] = p_ext[:bucket]
        cache1 = self.engine.init_cache(1, self.max_len)
        st1 = self.engine.init_proposer_state(1, self.max_len)
        lengths1 = jnp.asarray([len(p_ext)], jnp.int32)
        self._key, sub = jax.random.split(self._key)
        fr = (jnp.asarray(req.frames)[None] if req.frames is not None
              else None)
        cache1, lengths1, base1, st1 = self._prefill_jit(
            self.params, self.proposer_params, jnp.asarray(toks), lengths1,
            cache1, sub, jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_p], jnp.float32), st1, fr)
        self.stats["prefill_calls"] += 1

        # scatter the single-row cache/state into this slot along each
        # leaf's batch axis (cache: 1; proposer state: as declared)
        def insert(big, one, axis):
            idx = [0] * big.ndim
            idx[axis] = slot_idx
            return jax.lax.dynamic_update_slice(big, one.astype(big.dtype),
                                                tuple(idx))
        # safe per-slot insert: v1 serial admission only ever runs on the
        # dense layout (paged serial admission routes through
        # _admit_batched), so every cache leaf has slot axis 1
        self.cache = jax.tree.map(lambda b, o: insert(b, o, 1),  # speclint: disable=pytree-axis
                                  self.cache, cache1)
        self.pstate = jax.tree.map(insert, self.pstate, st1, self._sax)
        self.lengths = self.lengths.at[slot_idx].set(lengths1[0])
        self.base = self.base.at[slot_idx].set(base1[0])
        self.n_out = self.n_out.at[slot_idx].set(0)

    def _push_table(self):
        """Push the host block-table mirror to its device cache leaf when
        dirty (the §12 analogue of the ``_slotmeta_dev`` refresh — tables
        change only at admission/reap, never inside a step)."""
        if self.paged and self._table_dirty:
            self.cache[PAGES_KEY]["table"] = jnp.asarray(self._table)
            self._table_dirty = False

    def _chunk_step(self):
        """Advance every mid-chunk slot by one ``chunk_size`` piece in a
        single ``suffix_prefill`` call (DESIGN.md §14).

        All chunking slots share one fixed [B, chunk] call shape — the
        per-iteration prefill work is bounded by B * chunk whatever the
        prompt length, and the decode step that follows in the same
        ``step_once`` keeps every active slot flowing.  A slot whose final
        chunk lands here becomes active (its base token and primed
        proposer state come from that last call, exactly like a prefix-
        cache suffix admission) and, under the paged layout, registers its
        prompt in the prefix registry.  Runs only while a slot is
        mid-chunk (``step_once`` checks)."""
        self._push_table()
        C = self.chunk
        stoks = np.zeros((self.B, C), np.int32)
        nv = np.ones((self.B,), np.int32)
        mlen = np.zeros((self.B,), np.int32)
        smask = np.zeros((self.B,), bool)
        finishing = []
        for i, cs in self._chunk_state.items():
            toks, pos = cs["toks"], cs["pos"]
            n = min(C, len(toks) - pos)
            stoks[i, :n] = toks[pos:pos + n]
            nv[i] = n
            mlen[i] = pos
            smask[i] = True
            cs["pos"] = pos + n
            if cs["pos"] >= len(toks):
                finishing.append(i)
        self._key, sub = jax.random.split(self._key)
        (self.cache, self.lengths, self.base, self.pstate,
         self.n_out) = self._suffix_jit(
            self.params, self.proposer_params, jnp.asarray(stoks),
            jnp.asarray(nv), jnp.asarray(mlen), sub, self.cache,
            self.lengths, self.base, self.pstate, self.n_out,
            jnp.asarray(smask), jnp.asarray(self._temp),
            jnp.asarray(self._topp))
        self.stats["chunk_calls"] += 1
        self.stats["prefill_calls"] += 1
        if self.cfg.num_ssm_layers:
            # every non-chunking slot ran this call masked: its recurrent
            # state came back from the §17 checkpoint restore
            self.stats["ssm_restores"] += int(self.B - smask.sum())
        for i, cs in self._chunk_state.items():
            self._len_host[i] = cs["pos"]
            self.stats["prefill_tokens"] += int(nv[i])
        for i in finishing:
            cs = self._chunk_state.pop(i)
            self._active[i] = True
            self._acc_ema[i] = 1.0
            self._slotmeta_dev = None
            if self.prefix is not None:
                self.prefix.register(cs["toks"], self._table[i], self.pool)

    # ---- optimistic allocation + preemption (host side, DESIGN.md §14) ----

    def _ensure_blocks(self):
        """Grow every active slot's block table to reach ``len + T + 2``
        rows before the decode step writes there (optimistic allocation's
        counterpart to §12's worst-case reserve).

        On pool exhaustion: evict registry-only prefix blocks first, then
        preempt the latest-submitted running request and retry — possibly
        preempting the very slot being grown (admission guarantees any
        admitted request fits an otherwise-empty pool, so the loop always
        terminates)."""
        T2 = self.engine.dtree.T + 2
        for i in range(self.B):
            if not self._active[i]:
                continue
            need = blocks_for(int(self._len_host[i]) + T2, self.page_size)
            have = len(self._slot_alloc.get(i, []))
            while need > have:
                short = need - have
                if short > self.pool.available and self.prefix is not None:
                    self.prefix.evict(self.pool, short - self.pool.available)
                fresh = self.pool.alloc(short)
                if fresh is None:
                    if not self._preempt_lowest():
                        raise RuntimeError(
                            "block pool exhausted with no preemptible "
                            "victim (DESIGN.md §14)")
                    if not self._active[i]:
                        break              # this very slot was the victim
                    continue
                row = self._slot_alloc[i]
                self._table[i, have:need] = fresh
                row.extend(fresh)
                self._table_dirty = True
                self.stats["grown_blocks"] += len(fresh)
                have = need
        self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                        self.pool.in_use)

    def _preempt_lowest(self) -> bool:
        """Preempt the lowest-priority preemptible tenant (priority =
        submission order, so the latest rid goes first).  False if no
        tenant can be preempted."""
        cand = sorted((i for i, s in enumerate(self.slots)
                       if s.request is not None),
                      key=lambda i: self.slots[i].request.rid, reverse=True)
        for i in cand:
            if self._preempt(i):
                return True
        return False

    def _preempt(self, i: int) -> bool:
        """Preempt-and-requeue slot ``i`` (DESIGN.md §14): release its
        blocks (prefix-registered ones survive in the registry for the
        resume to match), trim its proposer-state rows, and put the
        request back at the queue head with its delivered tokens folded
        into the resume prompt (``_effective``).  Returns False when the
        request could not be resumed losslessly (its extended prompt no
        longer fits a prefill bucket and chunking is off)."""
        req = self.slots[i].request
        if req is None:
            return False
        if not self.chunk and \
                len(req.prompt) + len(req.output) > self.buckets[-1]:
            return False
        req.preemptions += 1
        req.status = "queued"
        self.queue.appendleft(req)
        self.slots[i].request = None
        self._active[i] = False
        self._done_now[i] = False
        self._chunk_state.pop(i, None)
        self._len_host[i] = 0
        self._slotmeta_dev = None
        if self.paged:
            self.pool.free(self._slot_alloc.pop(i, []))
            self._table[i, :] = 0
            self._matched[i] = 0
            self._table_dirty = True
        keep = np.ones((self.B,), bool)
        keep[i] = False
        self.pstate = self._trim_jit(self.pstate, jnp.asarray(keep))
        self.stats["preemptions"] += 1
        return True

    def _pick_level(self):
        """Select this step's speculation level (DESIGN.md §14): move one
        level at a time on the active slots' mean acceptance EMA, with
        ``adapt_low``/``adapt_high`` hysteresis so the level doesn't
        thrash between adjacent graphs."""
        if not self.adaptive or not self._active.any():
            return
        mean = float(self._acc_ema[self._active].mean())
        if mean < self.sched.adapt_low and self._level > 0:
            self._level -= 1
        elif mean > self.sched.adapt_high and \
                self._level < len(self._levels) - 1:
            self._level += 1

    def _decode_step(self):
        """One jitted serving step (device) + the SlotSync host apply.

        Syncs exactly one small ``SlotSync`` struct back; the per-slot
        metadata device copies refresh only when host bookkeeping changed
        them (``_slotmeta_dev`` / the paged block table).  Mid-chunk slots
        (inactive, request attached) are skipped by the masked commit and
        by the host apply.  Under §14 the step may run a smaller graph
        from the adaptive family, and ``_ensure_blocks`` grows optimistic
        allocations (possibly preempting) before any write happens.

        Spans: ``serve.decode`` up to the step call's return (dispatch),
        ``serve.sync`` the one ``device_get``, ``serve.apply`` the rest."""
        with TraceAnnotation("serve.decode"):
            launched = self._launch_step()
        if launched is None:
            return
        sync, gamma = launched
        with TraceAnnotation("serve.sync"):
            # one transfer for the whole SlotSync (speclint trace-safety:
            # the old per-field np.asarray calls cost four device
            # round-trips per decode step)
            sync = jax.device_get(sync)
        with TraceAnnotation("serve.apply"):
            self._apply(sync, gamma)

    def _launch_step(self):
        """Dispatch the decode step; (device SlotSync, gamma), or None when
        no slot is active."""
        if self.paged and self.preemption:
            self._ensure_blocks()
        if not self._active.any():
            return None
        self._push_table()
        self._key, sub = jax.random.split(self._key)
        if self._slotmeta_dev is None:
            self._slotmeta_dev = (jnp.asarray(self._active),
                                  jnp.asarray(self._eos),
                                  jnp.asarray(self._maxnew),
                                  jnp.asarray(self._temp),
                                  jnp.asarray(self._topp))
        active, eos, maxnew, temp, topp = self._slotmeta_dev
        self._pick_level()
        gamma, _ = self._levels[self._level]
        step_fn = (self._step_jits[self._level] if self.adaptive
                   else self._step_jit)
        (self.cache, self.lengths, self.base, self.pstate,
         self.n_out, sync) = step_fn(
            self.params, self.proposer_params, self.cache, self.lengths,
            self.base, self.pstate, self.n_out, sub, active, eos,
            maxnew, temp, topp)
        self.stats["steps"] += 1
        self.stats["gamma_steps"][gamma] += 1
        self.stats["slot_steps"] += int(self._active.sum())
        self.stats["lm_head_passes"] += self._head_passes[self._level]
        if self.cfg.num_ssm_layers:
            # masked slots (empty / mid-chunk) restored their SSM state
            # from the speculation-root checkpoint this step (§17)
            self.stats["ssm_restores"] += int((~self._active).sum())
        return sync, gamma

    def _apply(self, sync: SlotSync, gamma: int):
        """Apply a fetched ``SlotSync`` on the host: the committed-length
        mirror, the acceptance EMA and counter, and each active slot's new
        tokens (stamping ``first_token_at`` on a request's first)."""
        acc, toks, spec_acc = sync.acc, sync.tokens, sync.spec_acc
        self._done_now = np.array(sync.done)   # copy: host-mutated at reap
        # committed-length mirror + acceptance EMA (§14): spec_acc is the
        # raw verifier acceptance = exactly what commit advanced by (0 on
        # inactive slots)
        self._len_host[self._active] += spec_acc[self._active]
        self.stats["accepted_tokens"] += int(spec_acc.sum())
        d = self.sched.accept_ema
        ratio = (spec_acc - 1.0) / max(gamma, 1)
        self._acc_ema[self._active] = (
            d * self._acc_ema[self._active]
            + (1.0 - d) * ratio[self._active])
        now = time.monotonic()
        for i, slot in enumerate(self.slots):
            req = slot.request
            if req is None or not self._active[i]:
                continue
            req.steps += 1
            if req.first_token_at is None and acc[i]:
                req.first_token_at = now
            req.output.extend(int(t) for t in toks[i, : acc[i]])

    def _reap(self):
        """Batch-reap every slot the device marked done plus host-side
        stragglers; freed slots refill together on the next ``_admit``.
        A ``serve.reap`` span carries the slots freed."""
        with TraceAnnotation("serve.reap") as span:
            freed = self._reap_slots()
            if freed:
                span.set_metadata(freed=len(freed))

    def _reap_slots(self) -> List[int]:
        """The reap itself; returns the freed slots."""
        now = time.monotonic()
        freed = []
        for i, slot in enumerate(self.slots):
            req = slot.request
            if req is None:
                continue
            finished = bool(self._done_now[i])
            straggler = ((req.deadline_s and now - req.submitted_at > req.deadline_s)
                         or (req.max_steps and req.steps >= req.max_steps))
            if finished or straggler:
                # device already clipped output at the EOS token / budget
                req.status = "done" if finished else "cancelled"
                self.done[req.rid] = req
                slot.request = None
                freed.append(i)
        if freed:
            self._active[freed] = False
            self._done_now[freed] = False
            self._slotmeta_dev = None
            if self.paged:
                # return the slot's blocks (refcount 0 -> free list; blocks
                # a prefix registration or another slot still references
                # survive) and zero the table row so the freed slot's dead
                # writes inside the static step sink into the trash block
                for i in freed:
                    alloc = self._slot_alloc.pop(i, [])
                    # §14 reclaimed-block accounting: under worst-case
                    # reservation an early EOS strands the tail of the
                    # up-front reserve — surface how many blocks the
                    # request reserved but never wrote
                    used = blocks_for(int(self._len_host[i]), self.page_size)
                    self.stats["reclaimed_blocks"] += max(0,
                                                          len(alloc) - used)
                    self.pool.free(alloc)
                    self._table[i, :] = 0
                    self._matched[i] = 0
                self._table_dirty = True
            for i in freed:
                # a straggler-cancelled request may still be mid-chunk
                self._chunk_state.pop(i, None)
                self._len_host[i] = 0
                self._acc_ema[i] = 1.0
        return freed

    def _recover(self):
        """Node-failure recovery: re-queue all in-flight work (their caches
        are lost), reset device state.  Mid-chunk slots re-queue like any
        other in-flight request — their chunk cursors die with the cache
        (DESIGN.md §14), and delivered-output state is cleared so the
        retry is a plain from-scratch admission, not a resume."""
        for slot in self.slots:
            if slot.request is not None:
                req = slot.request
                req.retries += 1
                if req.retries > self.max_retries:
                    req.status = "failed"
                    self.done[req.rid] = req
                else:
                    req.output = []
                    req.steps = 0
                    req.status = "queued"
                    self.queue.appendleft(req)
                slot.request = None
        # rebuild EVERY donated device array: a failure raised after the
        # jitted step dispatched has already invalidated the old buffers
        self._reset_device_state()
        self._reset_host_slots()
        self._level = len(self._levels) - 1

    def _reset_device_state(self):
        """(Re)create all per-slot device arrays that jitted calls donate
        — including the proposer's opaque state pytree — plus, under the
        paged layout, the host allocator state they mirror (block pool,
        table mirror, prefix registry): after a recovery the device pool
        contents are gone, so every host claim about block ownership must
        be dropped with them."""
        if self.paged:
            self.pool = BlockPool(self.n_blocks)
            self.prefix = (PrefixCache(self.page_size)
                           if self.prefix_enabled else None)
            self._table = np.zeros((self.B, self.blocks_per_slot), np.int32)
            self._table_dirty = False
            self._slot_alloc: Dict[int, list] = {}
            self._matched = np.zeros((self.B,), np.int32)
            self.cache = self.engine.init_cache(self.B, self.max_len,
                                                n_blocks=self.n_blocks)
        else:
            self.prefix = None
            self.cache = self.engine.init_cache(self.B, self.max_len)
        self.lengths = jnp.ones((self.B,), jnp.int32)
        self.base = jnp.zeros((self.B,), jnp.int32)
        self.pstate = self.engine.init_proposer_state(self.B, self.max_len)
        self._sax = self.engine.proposer.state_axes(self.pstate)
        self.n_out = jnp.zeros((self.B,), jnp.int32)


class FamilySpecServer:
    """Per-request proposer choice behind one serving façade (DESIGN.md §17).

    Slot-group partitioning: each named group is a full ``SpecServer`` lane
    owning its engine (proposer + compiled step graphs, including the §14
    adaptive-speculation graph family), its model params, its cache (dense
    rows or a paged pool) and its slots — so one deployment mixes, say,
    chat traffic through a Medusa lane, code traffic through the train-free
    n-gram lane and transcription traffic through a draft-model or encdec
    lane, and no lane's compiled step shape constrains another's.

    ``submit(..., group=...)`` routes a request to its lane (default: the
    first group); ``step_once`` advances every busy lane, so lanes
    interleave at scheduler-iteration granularity.  Façade request ids are
    lane-independent — results resolve here, never against a lane directly.

    Groups over the same config may share one ``params`` pytree (the arrays
    are read-only inside jitted calls); groups over different configs —
    e.g. an encdec transcription lane beside decoder-only chat lanes — are
    simply different lanes.
    """

    def __init__(self, groups: Dict[str, SpecServer],
                 default: Optional[str] = None):
        if not groups:
            raise ValueError("FamilySpecServer needs at least one slot group")
        self.groups: Dict[str, SpecServer] = dict(groups)
        self.default = next(iter(self.groups)) if default is None else default
        if self.default not in self.groups:
            raise ValueError(f"default group {self.default!r} not in "
                             f"{sorted(self.groups)}")
        self._rid = 0
        self._route: Dict[int, tuple] = {}

    def submit(self, prompt: np.ndarray, max_new: int,
               group: Optional[str] = None, **kw) -> int:
        name = self.default if group is None else group
        if name not in self.groups:
            raise KeyError(f"unknown slot group {name!r}; have "
                           f"{sorted(self.groups)}")
        inner = self.groups[name].submit(prompt, max_new, **kw)
        self._rid += 1
        self._route[self._rid] = (name, inner)
        return self._rid

    def result(self, rid: int) -> Optional[Request]:
        route = self._route.get(rid)
        if route is None:
            return None
        name, inner = route
        return self.groups[name].result(inner)

    def group_of(self, rid: int) -> Optional[str]:
        route = self._route.get(rid)
        return None if route is None else route[0]

    @property
    def busy(self) -> bool:
        return any(srv.busy for srv in self.groups.values())

    def step_once(self, it: int = 0):
        """One façade iteration: advance every lane with work in flight.
        Idle lanes cost nothing — no jitted call is dispatched for them."""
        for srv in self.groups.values():
            if srv.busy:
                srv.step_once(it=it)

    def run(self, max_iters: int = 10_000) -> int:
        it = 0
        while self.busy and it < max_iters:
            self.step_once(it)
            it += 1
        return it

    def release_all(self):
        for srv in self.groups.values():
            srv.release_all()

    def reset(self):
        for srv in self.groups.values():
            srv.reset()
        self._route.clear()
        self._rid = 0

    @property
    def stats(self) -> Dict[str, dict]:
        """Per-lane stats keyed by group name (lanes are independent
        servers; summing across heterogeneous lanes would hide which
        proposer did the work)."""
        return {name: srv.stats for name, srv in self.groups.items()}


# Backwards-compatible name from before the pluggable-proposer refactor
# (DESIGN.md §13): the server was Medusa-only when it was christened.
MedusaServer = SpecServer
