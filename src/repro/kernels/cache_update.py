"""Pallas TPU kernel: in-place KV-cache commit (traffic model: DESIGN.md
§6; bytes/step accounting: DESIGN.md §10).

The pure-XLA commit (gather + select) rewrites the whole cache shard every
step (read+write = 2 full passes over k and v).  On TPU the committed rows
are a tiny window at a per-batch dynamic offset, so the right tool is an
aliased HBM ref + per-row async DMA: traffic drops from O(cache) to
O(K+1 rows).  ``input_output_aliases`` makes the write truly in-place.

Validated in interpret mode against the XLA formulation (tests); the
roofline's optimized-decode memory term uses this traffic model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_default


def _kernel(lens_ref, rows_ref, cache_ref, out_ref, sem, *, K1: int):
    b = pl.program_id(0)
    start = lens_ref[b]
    cp = pltpu.make_async_copy(
        rows_ref.at[0], out_ref.at[b, pl.ds(start, K1)], sem)
    cp.start()
    cp.wait()


def commit_rows(cache, rows, lengths, *, interpret: bool | None = None):
    """cache [B, S, H, D] any dtype (donated), rows [B, K1, H, D] (cast to
    cache dtype), lengths [B] int32.  Writes rows at
    [lengths[b], lengths[b]+K1) in place via per-row async DMA; returns
    cache.  Traffic is O(K1 rows), not O(cache)."""
    if interpret is None:
        interpret = interpret_default()
    B, S, H, D = cache.shape
    K1 = rows.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K1, H, D), lambda b, lens: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, K1=K1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},   # cache arg -> output (in-place)
        interpret=interpret,
        name="commit_rows",
    )
    return fn(lengths, rows.astype(cache.dtype), cache)


def commit_rows_stacked(cache, rows, lengths, **kw):
    """cache [nu, B, S, H, D], rows [nu, B, K1, H, D], lengths [B] int32:
    fold nu into B and commit in one grid."""
    nu, B = cache.shape[:2]
    out = commit_rows(cache.reshape((nu * B,) + cache.shape[2:]),
                      rows.reshape((nu * B,) + rows.shape[2:]),
                      jnp.tile(lengths, nu), **kw)
    return out.reshape(cache.shape)


def _kernel_paged(lens_ref, tbl_ref, rows_ref, pool_ref, out_ref, sem,
                  *, K1: int, ps: int, mb: int):
    b = pl.program_id(0)
    start = lens_ref[b]
    for j in range(K1):                     # K1 static: unrolled row DMAs
        pos = start + j
        lb = pos // ps
        # rows past the table's reach sink into the trash block (paging.py)
        blk = jnp.where(lb < mb, tbl_ref[b, jnp.minimum(lb, mb - 1)], 0)
        cp = pltpu.make_async_copy(
            rows_ref.at[0, j], out_ref.at[blk, pos % ps], sem)
        cp.start()
        cp.wait()


def commit_rows_paged(pool, block_tables, rows, lengths, *,
                      interpret: bool | None = None):
    """In-place commit through a block table (the paged layout, DESIGN.md
    §12).

    pool [n_blocks, page_size, H, D] any dtype (donated), block_tables
    [B, max_blocks] int32, rows [B, K1, H, D] (cast to pool dtype),
    lengths [B] int32.  Each committed row lands at physical row
    ``(block_tables[b, pos//ps], pos%ps)`` for pos in
    [lengths[b], lengths[b]+K1) — K1 per-row async DMAs per slot (rows may
    straddle a block boundary), still O(K1 rows) of traffic.  Rows beyond
    the table's reach sink into reserved block 0.  Returns pool."""
    if interpret is None:
        interpret = interpret_default()
    n_blocks, ps, H, D = pool.shape
    B, K1 = rows.shape[:2]
    mb = block_tables.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K1, H, D), lambda b, lens, tbl: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel_paged, K1=K1, ps=ps, mb=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},   # pool arg -> output (in-place)
        interpret=interpret,
        name="commit_rows_paged",
    )
    return fn(lengths, block_tables.astype(jnp.int32),
              rows.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# fused qkv projection + rope + tree-row cache write (DESIGN.md §15)
# ---------------------------------------------------------------------------

def _rope_half(x, cos, sin):
    """The exact ``layers.apply_rope`` op sequence on [T, H, hd] in-kernel:
    halves to f32, rotate, concatenate, cast back."""
    hd = x.shape[-1]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    dt = x.dtype
    x1, x2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    c, s = cos[:, None, :], sin[:, None, :]            # [T, 1, hd/2]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def _fused_qkv_body(lens_ref, tbl_ref, refs, *, T: int, Hq: int, Hkv: int,
                    hd: int, has_bias: bool, use_rope: bool, ps: int,
                    mb: int):
    it = iter(refs)
    x_ref, wq_ref, wk_ref, wv_ref = next(it), next(it), next(it), next(it)
    bq_ref = bk_ref = bv_ref = None
    if has_bias:
        bq_ref, bk_ref, bv_ref = next(it), next(it), next(it)
    cos_ref = sin_ref = None
    if use_rope:
        cos_ref, sin_ref = next(it), next(it)
    _kc_in, _vc_in = next(it), next(it)                # aliased; written via out
    q_out, k_out, v_out, kc_out, vc_out = (next(it) for _ in range(5))
    sem = next(it)

    b = pl.program_id(0)
    x = x_ref[0]                                       # [T, d]

    def proj(w_ref, b_ref, H):
        # [T, d] x [d, H*hd] with f32 accumulation, rounded to the
        # activation dtype — elementwise the einsum in ``_project_qkv``
        z = jax.lax.dot_general(x, w_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        z = z.astype(x.dtype).reshape(T, H, hd)
        if b_ref is not None:
            z = z + b_ref[...].astype(x.dtype)
        return z

    q = proj(wq_ref, bq_ref, Hq)
    k = proj(wk_ref, bk_ref, Hkv)
    v = proj(wv_ref, bv_ref, Hkv)
    if use_rope:
        cos, sin = cos_ref[0], sin_ref[0]              # [T, hd/2]
        q = _rope_half(q, cos, sin)
        k = _rope_half(k, cos, sin)
    q_out[0] = q
    k_out[0] = k
    v_out[0] = v

    start = lens_ref[b]
    if tbl_ref is not None:
        for j in range(T):                  # T static: unrolled row DMAs
            pos = start + j
            lb = pos // ps
            blk = jnp.where(lb < mb, tbl_ref[b, jnp.minimum(lb, mb - 1)], 0)
            for src, dst in ((k_out, kc_out), (v_out, vc_out)):
                cp = pltpu.make_async_copy(
                    src.at[0, j], dst.at[blk, pos % ps], sem)
                cp.start()
                cp.wait()
    else:
        for src, dst in ((k_out, kc_out), (v_out, vc_out)):
            cp = pltpu.make_async_copy(
                src.at[0], dst.at[b, pl.ds(start, T)], sem)
            cp.start()
            cp.wait()


def _fused_qkv_dense(lens_ref, *refs, T, Hq, Hkv, hd, has_bias, use_rope):
    _fused_qkv_body(lens_ref, None, refs, T=T, Hq=Hq, Hkv=Hkv, hd=hd,
                    has_bias=has_bias, use_rope=use_rope, ps=0, mb=0)


def _fused_qkv_paged(lens_ref, tbl_ref, *refs, T, Hq, Hkv, hd, has_bias,
                     use_rope, ps, mb):
    _fused_qkv_body(lens_ref, tbl_ref, refs, T=T, Hq=Hq, Hkv=Hkv, hd=hd,
                    has_bias=has_bias, use_rope=use_rope, ps=ps, mb=mb)


def fused_qkv_rope_commit(x, p, lengths, k_cache, v_cache, *, cos=None,
                          sin=None, table=None,
                          interpret: bool | None = None):
    """One kernel launch per unit for the decode step's write side
    (DESIGN.md §15): qkv projection, rope, and the tree-row cache write.

    x [B, T, d] normed activations; p: attention params with wq [d, Hq, hd],
    wk/wv [d, Hkv, hd] (+ bq/bk/bv); lengths [B] int32; cos/sin [B, T, hd/2]
    f32 precomputed rope tables (None when ``cfg.use_rope`` is off).  Dense:
    k_cache/v_cache [B, S, Hkv, hd] fp (donated), rows land at
    [lengths, lengths+T) via in-place async DMA.  Paged: pool-form caches
    [n_blocks, page_size, Hkv, hd] written through ``table``
    [B, max_blocks] with overflow sinking into trash block 0 — the same
    write rules as ``commit_rows_paged`` / ``paging.scatter_rows``.

    Returns (q, k, v [B, T, H*, hd] in x.dtype, k_cache', v_cache').
    The fp-only fast path: int8 caches keep the unfused projection (the
    quantize hop needs the scale cache — DESIGN.md §10)."""
    if interpret is None:
        interpret = interpret_default()
    B, T, d = x.shape
    Hq, hd = p["wq"].shape[1:]
    Hkv = p["wk"].shape[1]
    has_bias = "bq" in p
    use_rope = cos is not None
    paged = table is not None
    assert k_cache.dtype == x.dtype, "fused write path is fp-only"

    n_sp = 2 if paged else 1
    rep = lambda *blk: (lambda b, *_: blk)            # replicated operand
    per_b = lambda *blk: (lambda b, *_: (b,) + blk)
    in_specs = [pl.BlockSpec((1, T, d), per_b(0, 0)),
                pl.BlockSpec((d, Hq * hd), rep(0, 0)),
                pl.BlockSpec((d, Hkv * hd), rep(0, 0)),
                pl.BlockSpec((d, Hkv * hd), rep(0, 0))]
    inputs = [x, p["wq"].astype(x.dtype).reshape(d, Hq * hd),
              p["wk"].astype(x.dtype).reshape(d, Hkv * hd),
              p["wv"].astype(x.dtype).reshape(d, Hkv * hd)]
    if has_bias:
        in_specs += [pl.BlockSpec((Hq, hd), rep(0, 0)),
                     pl.BlockSpec((Hkv, hd), rep(0, 0)),
                     pl.BlockSpec((Hkv, hd), rep(0, 0))]
        inputs += [p["bq"], p["bk"], p["bv"]]
    if use_rope:
        half = hd // 2
        in_specs += [pl.BlockSpec((1, T, half), per_b(0, 0)),
                     pl.BlockSpec((1, T, half), per_b(0, 0))]
        inputs += [cos, sin]
    kc_idx = n_sp + len(inputs)
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    inputs += [k_cache, v_cache]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_sp,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, T, Hq, hd), per_b(0, 0, 0)),
            pl.BlockSpec((1, T, Hkv, hd), per_b(0, 0, 0)),
            pl.BlockSpec((1, T, Hkv, hd), per_b(0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((B, T, Hq, hd), x.dtype),
        jax.ShapeDtypeStruct((B, T, Hkv, hd), x.dtype),
        jax.ShapeDtypeStruct((B, T, Hkv, hd), x.dtype),
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
    ]
    kw = dict(T=T, Hq=Hq, Hkv=Hkv, hd=hd, has_bias=has_bias,
              use_rope=use_rope)
    if paged:
        body = functools.partial(_fused_qkv_paged, ps=k_cache.shape[1],
                                 mb=table.shape[1], **kw)
    else:
        body = functools.partial(_fused_qkv_dense, **kw)
    fn = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases={kc_idx: 3, kc_idx + 1: 4},
        interpret=interpret,
        name="fused_qkv_rope_commit",
    )
    if paged:
        return fn(lengths, table.astype(jnp.int32), *inputs)
    return fn(lengths, *inputs)


def commit_rows_quantized(cache, scale_cache, rows, lengths, **kw):
    """In-place commit into the int8 cache layout (DESIGN.md §10).

    cache [B, S, H, D] int8 (donated), scale_cache [B, S, H, 1] f32
    (donated), rows [B, K1, H, D] fp, lengths [B] int32.  Quantization is
    fused into the commit path: rows quantize once on-device and the two
    per-row async-DMA writes (values + scales) replace the single fp write —
    total committed traffic O(K1 rows) at ~half the fp byte count.
    Returns (cache, scale_cache).
    """
    from repro.kernels.quant import quantize_rows
    qrows, srows = quantize_rows(rows)
    return (commit_rows(cache, qrows, lengths, **kw),
            commit_rows(scale_cache, srows, lengths, **kw))


def commit_rows_paged_quantized(pool, scale_pool, block_tables, rows,
                                lengths, **kw):
    """Fused quantize + paged commit: int8 value pool
    [n_blocks, page_size, H, D] + f32 scale pool [n_blocks, page_size, H, 1]
    (both donated), rows [B, K1, H, D] fp — the int8 write fusion of
    DESIGN.md §10 through the block table of §12.  Returns
    (pool, scale_pool)."""
    from repro.kernels.quant import quantize_rows
    qrows, srows = quantize_rows(rows)
    return (commit_rows_paged(pool, block_tables, qrows, lengths, **kw),
            commit_rows_paged(scale_pool, block_tables, srows, lengths, **kw))
