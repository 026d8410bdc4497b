"""Hand-rolled collective-compute overlap primitives (shard_map level).

``ag_matmul`` computes ``all_gather(x, axis) @ W`` as a ring: each step
multiplies the currently held x-chunk against the matching W row-block while
the next chunk is in flight on a ``collective_permute`` — the pattern XLA's
latency-hiding scheduler overlaps (the TPU analogue of the paper's concern
that communication must never stall the static pipeline).  Used as a
drop-in for TP projections in the distributed-optimization work of
DESIGN.md §7.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def shard_map_compat(f, *, mesh, in_specs, out_specs, check: bool = True):
    """``jax.shard_map`` with its replication check (``check_vma``) as one
    boolean — the single call-site syntax every shard_map in the repo uses
    (and the name speclint's shard-specs rule keys on)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def ag_matmul_local(x_loc, w, axis_name: str):
    """Inside shard_map: x_loc [..., k_loc] (sharded on its last dim over
    ``axis_name``), w [k_glob, n] (replicated or col-shard of a larger W).
    Returns allgather(x) @ w without materialising the gathered x."""
    N = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    k_loc = x_loc.shape[-1]
    chunk = x_loc
    y = jnp.zeros(x_loc.shape[:-1] + (w.shape[-1],),
                  jnp.promote_types(x_loc.dtype, w.dtype))
    perm = [(i, (i - 1) % N) for i in range(N)]   # receive the next chunk
    for step in range(N):
        src = (idx + step) % N                    # global chunk currently held
        w_rows = jax.lax.dynamic_slice_in_dim(w, src * k_loc, k_loc, axis=0)
        y = y + jnp.einsum("...k,kn->...n", chunk, w_rows)
        if step != N - 1:
            chunk = jax.lax.ppermute(chunk, axis_name, perm)
    return y


def ag_matmul(x, w, mesh: Mesh, axis_name: str = "model"):
    """pjit-level wrapper: x sharded on last dim over ``axis_name``."""
    fn = shard_map_compat(
        functools.partial(ag_matmul_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(*(None,) * (x.ndim - 1), axis_name), P(None, None)),
        out_specs=P(*(None,) * x.ndim),
        check=False,   # result is replicated after the full ring
    )
    return fn(x, w)
