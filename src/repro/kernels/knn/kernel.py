"""Pallas TPU kernel: per-query-block candidate distances + top-k selection.

This is the inner loop of device-resident graph construction (the paper's
"custom graphs directly from tessellated geometry" promise, served in real
time): for each block of query points, compute squared distances to the
fixed-size candidate list emitted by the hash-grid cell search, then select
the k nearest with an unrolled argmin loop (k is small and static — 6 in the
paper). The candidate-id gather for the winner uses the same one-hot trick as
the ``segment_agg`` kernel: ``sum(onehot * cand_idx)`` never leaves VMEM.

Layout: coordinates arrive as three (N, C) planes (x, y, z) plus a (N, 4)
query tile — 2D arrays with a 128-aligned candidate lane dimension, so blocks
map cleanly onto VPU tiles. Grid: (query_blocks,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128      # query points per block
_BIG = 1e30                # invalid-candidate sentinel (matches ref.py)


def _knn_kernel(q_ref, cx_ref, cy_ref, cz_ref, cidx_ref, cvalid_ref,
                idx_ref, d2_ref, *, k: int):
    """q_ref: (BQ, 4) query xyz (+pad); c*_ref: (BQ, C) candidate coordinate
    planes; cidx_ref: (BQ, C) i32 ids; cvalid_ref: (BQ, C) f32 1=real.
    idx_ref/d2_ref: (BQ, k) outputs."""
    q = q_ref[...]
    dx = cx_ref[...] - q[:, 0:1]
    dy = cy_ref[...] - q[:, 1:2]
    dz = cz_ref[...] - q[:, 2:3]
    d2 = dx * dx + dy * dy + dz * dz
    d2 = jnp.where(cvalid_ref[...] > 0, d2, _BIG)
    cidx = cidx_ref[...]
    cols = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    for j in range(k):                      # k is static: unrolled
        m = jnp.min(d2, axis=1)             # (BQ,)
        am = jnp.argmin(d2, axis=1).astype(jnp.int32)
        onehot = cols == am[:, None]        # (BQ, C)
        sel = jnp.sum(jnp.where(onehot, cidx, 0), axis=1)
        found = m < _BIG * 0.5
        idx_ref[:, j] = jnp.where(found, sel, -1)
        d2_ref[:, j] = jnp.where(found, m, _BIG)
        d2 = jnp.where(onehot, _BIG, d2)    # knock out the winner


def knn_topk_call(q_pos4, cand_x, cand_y, cand_z, cand_idx, cand_valid,
                  k: int, *, block_q: int = DEFAULT_BLOCK_Q):
    """q_pos4: (N, 4) f32; cand_*: (N, C); N must be a multiple of block_q.

    Returns (idx (N, k) i32, d2 (N, k) f32). On the CPU backend the kernel
    body runs in the Pallas interpreter; on an accelerator it compiles to a
    Mosaic custom call."""
    n, c = cand_idx.shape
    assert n % block_q == 0, (n, block_q)
    grid = (n // block_q,)
    row_spec = pl.BlockSpec((block_q, c), lambda i: (i, 0))
    out_spec = pl.BlockSpec((block_q, k), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_knn_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 4), lambda i: (i, 0)),
            row_spec, row_spec, row_spec, row_spec, row_spec,
        ],
        out_specs=(out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((n, k), jnp.int32),
            jax.ShapeDtypeStruct((n, k), jnp.float32),
        ),
        interpret=jax.default_backend() == "cpu",
    )(q_pos4, cand_x, cand_y, cand_z, cand_idx, cand_valid)
