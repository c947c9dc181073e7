"""Jitted wrapper for the kNN top-k kernel with an XLA reference fallback.

``topk_neighbors`` is fully shape-static and jittable: callers pass padded
fixed-size candidate lists and get back fixed-degree (N, k) neighbor indices
plus a validity mask. ``impl='xla'`` uses the pure-jnp oracle (fast under XLA
on CPU/GPU); ``impl='pallas'`` routes through the TPU kernel, padding the
query and candidate dimensions to tile-aligned sizes.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.knn import ref
from repro.kernels.knn.kernel import DEFAULT_BLOCK_Q, knn_topk_call


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def topk_neighbors(q_pos, cand_pos, cand_idx, cand_valid, k: int, *,
                   impl: str = "xla", block_q: int = DEFAULT_BLOCK_Q):
    """Dispatch to the Pallas kernel or the XLA reference.

    q_pos: (N, 3); cand_pos: (N, C, 3); cand_idx: (N, C) i32 (safe values in
    invalid slots); cand_valid: (N, C) bool.
    Returns (idx (N, k) i32 with -1 missing, d2 (N, k) f32, mask (N, k) bool).
    """
    if impl == "xla":
        return ref.topk_neighbors(q_pos, cand_pos, cand_idx, cand_valid, k)
    if impl != "pallas":
        raise ValueError(f"unknown knn impl {impl!r}")

    n, c = cand_idx.shape
    n_pad = _round_up(max(n, 1), block_q)
    c_pad = _round_up(max(c, 1), 128)      # lane-align the candidate dim
    q4 = jnp.pad(q_pos.astype(jnp.float32), ((0, n_pad - n), (0, 1)))
    cp = jnp.pad(cand_pos.astype(jnp.float32),
                 ((0, n_pad - n), (0, c_pad - c), (0, 0)))
    ci = jnp.pad(cand_idx.astype(jnp.int32),
                 ((0, n_pad - n), (0, c_pad - c)))
    cv = jnp.pad(cand_valid.astype(jnp.float32),
                 ((0, n_pad - n), (0, c_pad - c)))
    idx, d2 = knn_topk_call(q4, cp[..., 0], cp[..., 1], cp[..., 2], ci, cv,
                            k, block_q=block_q)
    idx, d2 = idx[:n], d2[:n]
    mask = idx >= 0
    return idx, d2, mask
