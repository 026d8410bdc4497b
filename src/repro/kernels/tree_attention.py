"""Pallas TPU kernel: flash-decoding attention over a KV cache with
per-row lengths — the compute hot spot of the paper's static tree
verification step (and of the AR baseline).

TPU adaptation of the paper's fused NPU verification operator
(DESIGN.md §6): instead of a CUDA-style dynamic kernel, the cache sweep is
a static grid over KV blocks with an online-softmax carry held in VMEM
scratch; per-batch ``lengths`` arrive via scalar prefetch so block skipping
and masking are computed on-chip without any host sync.  The (tiny) tree
block itself is handled by the wrapper in ``ops.py`` and merged with the
partial-softmax stats this kernel emits — the merge is exact.

Layout: q is folded to [B, Hkv, R, D] with R = G*T rows (G = q heads per
kv head, T = tree size padded to a multiple of 8) so the MXU tile contracts
[R, D] x [D, BS] with hardware-aligned D (head_dim 64/128/256).

Int8 KV path (DESIGN.md §10): when k/v arrive as int8 with per-head-per-row
scales, each grid step DMAs the int8 block plus its [BS, 1] f32 scale
column in the same schedule and dequantizes in VMEM right before the MXU
dot — HBM traffic per step drops to ~(D+4)/(2*D) of the bf16 sweep while
the online-softmax math stays in f32 exactly as in the fp path.

Paged KV path (DESIGN.md §12): with ``block_tables`` the cache arrives as a
global block pool [n_blocks, Hkv, block_s, D] instead of per-batch rows, and
the kernel follows the per-slot table inside the sweep: the KV index map
reads ``block_tables[b, s]`` (a second scalar-prefetch operand, resolved
on-chip like ``lengths``) to pick the physical block for grid step ``s`` —
the same indirection the dense index map already performs for the
skip-refetch trick, now through one extra SMEM lookup.  The kernel body is
unchanged: masking still runs on logical columns ``s*block_s + i < length``,
and the int8 scale pools ride the identical table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref,                       # scalar prefetch [B] int32
            q_ref, k_ref, v_ref, *rest,        # VMEM blocks (+ scales if int8)
            block_s: int, n_s: int, quantized: bool):
    """One (b, h, s) grid step of the cache sweep.

    Block shapes (leading [1, 1] grid dims elided): q [R, D] f32/bf16
    (pre-scaled by 1/sqrt(D)); k/v [BS, D] — fp, or int8 with ks/vs [BS, 1]
    f32 scales; outputs acc [R, D] f32, m/l [R, 1] f32 partial-softmax stats.
    """
    if quantized:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref, m_scr, l_scr = rest
    else:
        out_ref, m_ref, l_ref, acc_ref, m_scr, l_scr = rest
    b = pl.program_id(0)
    s = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    s0 = s * block_s

    @pl.when(s0 < length)
    def _compute():
        q = q_ref[0, 0]                        # [R, D]  (pre-scaled)
        if quantized:
            # fused dequant in VMEM: int8 block * [BS, 1] f32 scale column
            k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
            v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0, 0]                    # [BS, D]
            v = v_ref[0, 0]                    # [BS, D]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [R, BS]
        col = s0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(col < length, scores, NEG_INF)

        m_prev = m_scr[...]                    # [R, 1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)            # [R, BS]
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(s == n_s - 1)
    def _emit():
        out_ref[0, 0] = acc_ref[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def _kernel_paged(lengths_ref, tables_ref, *rest, block_s: int, n_s: int,
                  quantized: bool):
    """Paged wrapper: the block table is consumed by the index maps only —
    the body's logical-column masking is layout-independent."""
    _kernel(lengths_ref, *rest, block_s=block_s, n_s=n_s, quantized=quantized)


def _fit_blocks(S: int, block_s: int):
    """(block_s', pad) such that block_s' divides S+pad and stays a multiple
    of 128 lanes.  Replaces the former hard ``S % block_s == 0`` assert: a
    non-multiple ``max_len`` (e.g. 640 with the default 512 block) now pads
    up to the next block boundary instead of crashing; padded columns sit at
    indices >= S >= lengths[b], so the in-kernel ``col < length`` mask
    already zeroes them and no separate pad mask is needed."""
    if S % block_s == 0:
        return block_s, 0
    if S < block_s:
        block_s = max(-(-S // 128) * 128, 128)  # clamp: one (padded) block
    return block_s, (-S) % block_s


def flash_decode(q, k, v, lengths, *, k_scale=None, v_scale=None,
                 block_tables=None, block_s: int = 512,
                 interpret: bool = False):
    """Partial-softmax decode attention over the committed cache region.

    q [B, Hkv, R, D] f32/bf16 (pre-scaled by 1/sqrt(D)); lengths [B] int32.

    Dense layout: k/v [B, Hkv, S, D] — fp (f32/bf16), or int8 with
    ``k_scale``/``v_scale`` [B, Hkv, S, 1] f32 per-head-per-row scales
    (DESIGN.md §10).  S need not be a multiple of ``block_s``; see
    ``_fit_blocks``.

    Paged layout (DESIGN.md §12): pass ``block_tables`` [B, max_blocks]
    int32 and the pool forms k/v [n_blocks, Hkv, page_size, D] (int8 scales
    [n_blocks, Hkv, page_size, 1]); ``block_s`` is the pool's page size and
    grid step ``s`` sweeps physical block ``block_tables[b, s]``.

    Returns un-normalised partial-softmax stats (acc [B, Hkv, R, D] f32,
    m/l [B, Hkv, R, 1] f32) for the exact tree-block merge in ``ops.py``.
    """
    B, Hkv, R, D = q.shape
    quantized = k.dtype == jnp.int8
    assert quantized == (k_scale is not None), (k.dtype, k_scale is None)
    paged = block_tables is not None
    if paged:
        block_s = k.shape[2]
        n_s = block_tables.shape[1]

        def kv_map(b, h, s, lens, tbl):
            # follow the slot's table; beyond-length steps are skipped in the
            # body — refetch the slot's first block so the DMA is a cheap
            # repeat (possibly the trash block for idle slots; never read).
            return (tbl[b, jnp.where(s * block_s < lens[b], s, 0)], h, 0, 0)

        def io_map(b, h, s, lens, tbl):
            return (b, h, 0, 0)
    else:
        S = k.shape[2]
        block_s, pad_s = _fit_blocks(S, block_s)
        if pad_s:
            pad = ((0, 0), (0, 0), (0, pad_s), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            if quantized:
                k_scale, v_scale = jnp.pad(k_scale, pad), jnp.pad(v_scale, pad)
            S += pad_s
        n_s = S // block_s

        def kv_map(b, h, s, lens):
            # beyond-length blocks are skipped in the body; refetch block 0
            # so the DMA is a cheap repeat instead of a dead fetch.
            return (b, h, jnp.where(s * block_s < lens[b], s, 0), 0)

        def io_map(b, h, s, lens):
            return (b, h, 0, 0)

    # dense and paged share the block geometry: (1, 1, block_s, D) slices of
    # [B, Hkv, S, D] or of the [n_blocks, Hkv, page_size, D] pool.
    in_specs = [
        pl.BlockSpec((1, 1, R, D), io_map),
        pl.BlockSpec((1, 1, block_s, D), kv_map),
        pl.BlockSpec((1, 1, block_s, D), kv_map),
    ]
    inputs = [q, k, v]
    if quantized:
        # scale columns ride the same index map as their k/v block, so the
        # pipeline prefetches them in lock-step with the int8 block DMA
        in_specs += [pl.BlockSpec((1, 1, block_s, 1), kv_map),
                     pl.BlockSpec((1, 1, block_s, 1), kv_map)]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if paged else 1,
        grid=(B, Hkv, n_s),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, R, D), io_map),
            pl.BlockSpec((1, 1, R, 1), io_map),
            pl.BlockSpec((1, 1, R, 1), io_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, D), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((B, Hkv, R, D), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, R, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, R, 1), jnp.float32),
    ]
    body = (functools.partial(_kernel_paged, block_s=block_s, n_s=n_s,
                              quantized=quantized) if paged else
            functools.partial(_kernel, block_s=block_s, n_s=n_s,
                              quantized=quantized))
    fn = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name="flash_decode",
    )
    if paged:
        return fn(lengths, block_tables.astype(jnp.int32), *inputs)
    return fn(lengths, *inputs)


# ---------------------------------------------------------------------------
# fused verify epilogue: unembed + acceptance statistics (DESIGN.md §15)
# ---------------------------------------------------------------------------

# Scoped VMEM the verify kernel asks for, and the budget its slot groups are
# sized against: a TPU v5e holds 128 MiB of VMEM and scopes 16 MiB to a
# kernel unless told otherwise.
VERIFY_VMEM_LIMIT = 48 << 20


def _default_block_v(V: int) -> int:
    return V if V <= 4096 else 512


def _verify_vmem_bytes(G: int, Tp: int, d: int, block_v: int,
                       itemsize: int) -> int:
    """VMEM of one grid step for a group of G slots: the double-buffered
    hidden rows and head block, the double-buffered per-row temperatures
    and outputs, the carry, and six f32 [G*Tp, block_v] temporaries of the
    body (logits, their bf16 round trip, warped logits, column ids, exp,
    one spare).  A lane dim narrower than 128 occupies 128."""
    rows, lane = G * Tp, 128
    dp = -(-d // lane) * lane
    cl = rows * (-(-Tp // lane) * lane) * 4
    return (2 * rows * dp * itemsize                # hidden rows
            + 2 * block_v * dp * itemsize           # head block
            + 2 * 4 * rows * lane * 4               # tmax in, argm/m/l out
            + 2 * cl                                # cand_w out
            + 3 * rows * lane * 4 + cl              # carry
            + 6 * rows * block_v * 4                # body temporaries
            + block_v * lane * 4)                   # one-hot


def verify_stats_plan(B: int, Tp: int, d: int, block_v: int, dtype, *,
                      max_group: int | None = None):
    """(G, block_v, vmem_bytes) for ``unembed_verify_stats``: the largest
    group of G <= B slots whose grid step fits ``VERIFY_VMEM_LIMIT``.

    Each group streams the whole lm head once, so a call makes
    ``cdiv(B, G)`` head passes; G is then evened out to the smallest group
    that makes as few passes (B = 32 that fits 18 runs two groups of 16).
    G = 1 is one pass per slot; ``max_group`` caps G (tests pin it to
    compare groupings).  When not even one slot fits, G is 1 and the
    compiler has the last word."""
    block_v = max(-(-block_v // 128) * 128, 128)
    itemsize = jnp.dtype(dtype).itemsize
    cap = B if max_group is None else max(1, min(B, max_group))
    G = next((g for g in range(cap, 1, -1) if _verify_vmem_bytes(
        g, Tp, d, block_v, itemsize) <= VERIFY_VMEM_LIMIT), 1)
    G = -(-B // -(-B // G))        # same passes, groups as even as they go
    return G, block_v, _verify_vmem_bytes(G, Tp, d, block_v, itemsize)


def lm_head_passes(B: int, T: int, d: int, V: int, dtype) -> int:
    """Passes over the lm head one ``unembed_verify_stats`` call makes for
    [B, T, d] hidden rows of ``dtype`` and a V-column head, as planned."""
    Tp = T + (-T % 8)
    G, _, _ = verify_stats_plan(B, Tp, d, _default_block_v(V), dtype)
    return -(-B // G)


def _verify_stats_kernel(cand_ref, h_ref, t_ref, w_ref,
                         argm_ref, m_ref, l_ref, cl_ref,
                         wmax_scr, lsum_scr, amax_scr, cl_scr,
                         *, block_v: int, n_v: int, V: int, T: int, G: int,
                         vocab_major: bool):
    """One (g, j) grid step of the vocab sweep for a group of G slots.

    Blocks: cand [G, 1, T] i32; h [G*T, d], the group's tree rows, resident
    across the sweep; t [G*T, 1] f32, each row's warp temperature; w
    [d, BV] (or [BV, d] rows of the transposed head when ``vocab_major``).

    Streams the lm-head matmul over vocab blocks, one [G*T, d] x [d, BV]
    product per step, so each block of the head leaves HBM once per group.
    Only Verdict-sized acceptance statistics stay in VMEM, per row:
    argmax (first-wins across blocks via a strict-greater merge), warped
    max ``m`` and sum-exp ``l`` (online softmax carry), and each slot's
    [T, T] candidate-logit table extracted by a one-hot matmul — exact,
    because each output element is one ``x * 1`` plus exact zeros.  The
    [G*T, BV] logits block dies in VMEM; nothing [*, V]-shaped reaches
    HBM.  The last vocab block may run past V: its out-of-range columns
    hold unspecified values and are masked to NEG_INF before any
    statistic reads them.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        wmax_scr[...] = jnp.full_like(wmax_scr, NEG_INF)
        lsum_scr[...] = jnp.zeros_like(lsum_scr)
        amax_scr[...] = jnp.zeros_like(amax_scr)
        cl_scr[...] = jnp.zeros_like(cl_scr)

    h = h_ref[...]                                 # [G*T, d]
    z = jax.lax.dot_general(
        h, w_ref[...], (((1,), (1 if vocab_major else 0,)), ((), ())),
        preferred_element_type=jnp.float32)        # [G*T, BV]
    # round through the activation dtype (bf16 configs) so the stats match
    # the unfused ``unembed`` einsum, then warp exactly as
    # ``sampling.warp_logits``: true division by the clamped temperature
    # (monotonic, so argmax is shared with raw logits)
    z = z.astype(h.dtype).astype(jnp.float32)
    wv = z / t_ref[...]
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, wv.shape, 1)
    wv = jnp.where(col < V, wv, NEG_INF)           # pad columns: exact no-ops

    bm = jnp.max(wv, axis=1, keepdims=True)        # [G*T, 1]
    bi = jnp.argmax(wv, axis=1)[:, None].astype(jnp.int32) + j * block_v
    m_prev = wmax_scr[...]
    amax_scr[...] = jnp.where(bm > m_prev, bi, amax_scr[...])
    m_new = jnp.maximum(m_prev, bm)
    alpha = jnp.exp(m_prev - m_new)
    lsum_scr[...] = lsum_scr[...] * alpha + jnp.sum(
        jnp.exp(wv - m_new), axis=1, keepdims=True)
    wmax_scr[...] = m_new

    for g in range(G):                             # each slot's candidates
        rows = slice(g * T, (g + 1) * T)
        rel = cand_ref[g] - j * block_v            # [1, T]
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (block_v, T), 0)
                  == rel).astype(jnp.float32)      # [BV, T]
        cl_scr[rows, :] += jax.lax.dot_general(
            wv[rows], onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)    # [T, T]

    @pl.when(j == n_v - 1)
    def _emit():
        for g in range(G):
            rows = slice(g * T, (g + 1) * T)
            argm_ref[g] = amax_scr[rows, :]
            m_ref[g] = wmax_scr[rows, :]
            l_ref[g] = lsum_scr[rows, :]
            cl_ref[g] = cl_scr[rows, :]


def unembed_verify_stats(hidden, w, candidates, tmax, *, block_v=None,
                         max_group: int | None = None,
                         interpret: bool = False):
    """Fused unembed + verify statistics (DESIGN.md §15).

    hidden [B, T, d]; w [d, V] lm-head weight; candidates [B, T] int32;
    tmax [B] f32 pre-clamped warp temperatures (``max(t, 1e-6)``, so the
    kernel's division matches ``sampling.warp_logits`` bit-for-bit).

    Returns (argm [B, T] int32, m [B, T] f32, l [B, T] f32,
    cand_w [B, T, T] f32) where ``cand_w[b, t, j]`` is the warped logit of
    candidate token ``j`` under node ``t``'s row — everything the greedy
    match and the residual-mass walk need, at O(T^2) instead of O(T*V)
    HBM traffic.

    Slots are folded into the kernel's rows in groups of G
    (``verify_stats_plan``; ``max_group`` caps G): the grid is
    ``(cdiv(B, G), n_v)``, and each group sweeps the head once, so a call
    reads the head ``cdiv(B, G)`` times — once when the group holds every
    slot.  Every statistic depends only on its own row, so G changes no
    result.  A B that is no multiple of G pads the hidden rows with zero
    slots, which are dropped from the outputs.

    The vocab sweep runs ``cdiv(V, block_v)`` blocks straight off ``w``: a
    V that is no multiple of ``block_v`` (openpangu's 153,376) leaves a
    ragged last block that the kernel masks, so the lm head is never
    padded.  Nor is it relaid out: XLA's TPU layout keeps a 2-D array's
    128-aligned dim minor, so a [d, V] head with V % 128 != 0 sits in HBM
    as its [V, d] transpose.  The kernel then reads ``w.T`` (a free
    bitcast) in [block_v, d] blocks instead of forcing a row-major copy of
    the whole head into every call.  Per-row outputs are laid out
    [B, T, 1] so every block's last two dims are (T, 1) — a multiple of 8
    and the full lane dim — which the TPU lowering requires for any B.

    When the vocab fits one block (the default for V <= 4096) the online
    carry degenerates to a single pass and ``exp(cand_w - m) / l`` is
    bitwise ``softmax(warped)`` gathered at the candidates; with multiple
    vocab blocks ``l`` picks up online-rescale rounding (~1 ulp) — the
    differential suite gates token-identity either way.
    """
    B, T, d = hidden.shape
    V = w.shape[1]
    Tp = T + (-T % 8)
    G, block_v, _ = verify_stats_plan(
        B, Tp, d, _default_block_v(V) if block_v is None else block_v,
        hidden.dtype, max_group=max_group)
    nG = pl.cdiv(B, G)
    Bp = nG * G
    if Bp > B or Tp > T:
        hidden = jnp.pad(hidden, ((0, Bp - B), (0, Tp - T), (0, 0)))
        candidates = jnp.pad(candidates, ((0, Bp - B), (0, Tp - T)))
        tmax = jnp.pad(tmax, (0, Bp - B), constant_values=1.0)
    n_v = pl.cdiv(V, block_v)
    vocab_major = bool(V % 128) and not d % 128
    if vocab_major:
        w = w.T
        w_spec = pl.BlockSpec((block_v, d), lambda g, j: (j, 0))
    else:
        w_spec = pl.BlockSpec((d, block_v), lambda g, j: (0, j))

    rows = pl.BlockSpec((G * Tp, 1), lambda g, j: (g, 0))
    per_row = pl.BlockSpec((G, Tp, 1), lambda g, j: (g, 0, 0))
    argm, m, l, cl = pl.pallas_call(
        functools.partial(_verify_stats_kernel, block_v=block_v, n_v=n_v,
                          V=V, T=Tp, G=G, vocab_major=vocab_major),
        grid=(nG, n_v),
        in_specs=[
            pl.BlockSpec((G, 1, Tp), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((G * Tp, d), lambda g, j: (g, 0)),
            rows,
            w_spec,
        ],
        out_specs=[per_row, per_row, per_row,
                   pl.BlockSpec((G, Tp, Tp), lambda g, j: (g, 0, 0))],
        scratch_shapes=[
            pltpu.VMEM((G * Tp, 1), jnp.float32),
            pltpu.VMEM((G * Tp, 1), jnp.float32),
            pltpu.VMEM((G * Tp, 1), jnp.int32),
            pltpu.VMEM((G * Tp, Tp), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Tp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, Tp, Tp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VERIFY_VMEM_LIMIT),
        interpret=interpret,
        name="unembed_verify_stats",
    )(candidates.astype(jnp.int32).reshape(Bp, 1, Tp),
      hidden.reshape(Bp * Tp, d),
      jnp.repeat(tmax.astype(jnp.float32), Tp)[:, None],
      w.astype(hidden.dtype))
    return argm[:B, :T, 0], m[:B, :T, 0], l[:B, :T, 0], cl[:B, :T, :T]
