"""Pallas TPU kernel: segment-sum (scatter-add) as a one-hot MXU matmul.

TPU adaptation of the GNN aggregation hot-spot (DESIGN.md S4). GPUs scatter
with atomics; TPUs have none, and random HBM access wastes bandwidth. We
instead sort edges by destination once (preprocessing in ops.py), pad each
node-block's message rows to a fixed count EBLK, and compute

    out[block] = one_hot(dest_local) @ messages[block]     # (BN,EBLK)@(EBLK,D)

on the MXU with explicit VMEM tiles. The one-hot is built in-kernel from the
destination ids via broadcasted_iota comparison — it never touches HBM.

Grid: (node_blocks, d_tiles). Padding rows carry dest=-1 and match no row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_N = 128      # nodes per block (MXU-aligned)
DEFAULT_BLOCK_D = 128      # feature tile


def _agg_kernel(dest_ref, msg_ref, out_ref, *, block_n: int):
    """dest_ref: (EBLK, 1) i32 local dest in [0, block_n) or -1 (padding);
    msg_ref: (EBLK, BD); out_ref: (BN, BD)."""
    eblk = dest_ref.shape[0]
    dest = dest_ref[...].reshape(1, eblk)                 # (1, EBLK)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, eblk), 0)
    onehot = (rows == dest).astype(msg_ref.dtype)         # (BN, EBLK)
    out_ref[...] = jnp.dot(
        onehot, msg_ref[...],
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def segment_agg_call(messages, dest_local, n_blocks: int,
                     *, block_n: int = DEFAULT_BLOCK_N,
                     block_d: int = DEFAULT_BLOCK_D):
    """messages: (NB*EBLK, D) sorted+padded by ops.prepare(); dest_local:
    (NB*EBLK, 1) i32, destination row within each node block (-1 = padding).
    Returns (NB*block_n, D) scatter-add result.

    On the CPU backend the kernel body runs in the Pallas interpreter; on
    an accelerator it compiles to a Mosaic custom call."""
    e_pad, d = messages.shape
    assert e_pad % n_blocks == 0
    eblk = e_pad // n_blocks
    assert d % block_d == 0 or d == block_d, (d, block_d)
    bd = min(block_d, d)
    grid = (n_blocks, d // bd)
    return pl.pallas_call(
        functools.partial(_agg_kernel, block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((eblk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((eblk, bd), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_n, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * block_n, d), messages.dtype),
        interpret=jax.default_backend() == "cpu",
    )(dest_local, messages)
