"""Jitted wrapper + preprocessing for the segment aggregation kernel.

Preparation (sort edges by destination, pad so every node block of
``block_n`` nodes owns a fixed number EBLK of message rows) runs ONCE per
graph; ``segment_sum_prepared()`` then runs per message-passing layer: an
XLA gather (permutation) + the Pallas one-hot-matmul kernel.

Two interchangeable preparers:

* ``prepare()`` — host numpy, sizes EBLK from the data (always exact);
  the training-time path where the graph is known up front.
* ``prepare_device()`` — pure jnp, jittable, fixed shapes: EBLK is a
  static argument (serving buckets have static edge budgets), packing is
  an argsort + one scatter. Runs *inside* the jitted points->prediction
  pipeline, which is what makes ``agg_impl='pallas'`` and the sorted-XLA
  path (``segment_sum_sorted``) usable in the serving hot path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segment_agg.kernel import (DEFAULT_BLOCK_D,
                                              DEFAULT_BLOCK_N,
                                              segment_agg_call)


@dataclass(frozen=True)
class SegmentPrep:
    perm: np.ndarray           # (NB*EBLK,) i32 indices into messages (0 for pad)
    perm_valid: np.ndarray     # (NB*EBLK, 1) f32 1=real row
    dest_local: np.ndarray     # (NB*EBLK, 1) i32 in-block dest, -1 for pad
    n_blocks: int
    block_n: int
    n_segments: int

    @property
    def pad_rows(self) -> int:
        return int(self.perm.shape[0])


def prepare(segment_ids: np.ndarray, num_segments: int,
            block_n: int = DEFAULT_BLOCK_N) -> SegmentPrep:
    """Sort edge->segment assignments into fixed-size per-node-block runs."""
    segment_ids = np.asarray(segment_ids)
    e = segment_ids.shape[0]
    nb = max(1, -(-num_segments // block_n))
    order = np.argsort(segment_ids, kind="stable")
    sorted_seg = segment_ids[order]
    block_of = sorted_seg // block_n
    counts = np.bincount(block_of, minlength=nb)
    eblk = int(counts.max()) if e else 1
    # round EBLK to a lane multiple for MXU efficiency
    eblk = max(128, int(-(-eblk // 128) * 128))
    perm = np.zeros((nb * eblk,), np.int32)
    valid = np.zeros((nb * eblk, 1), np.float32)
    dest = np.full((nb * eblk, 1), -1, np.int32)
    start = 0
    for b in range(nb):
        c = int(counts[b])
        rows = order[start:start + c]
        perm[b * eblk: b * eblk + c] = rows
        valid[b * eblk: b * eblk + c] = 1.0
        dest[b * eblk: b * eblk + c, 0] = segment_ids[rows] - b * block_n
        start += c
    return SegmentPrep(perm=perm, perm_valid=valid, dest_local=dest,
                       n_blocks=nb, block_n=block_n, n_segments=num_segments)


@dataclass(frozen=True)
class DeviceSegmentPrep:
    """Device-side twin of :class:`SegmentPrep` (all jnp, built under jit).

    ``n_dropped`` is a traced scalar: the number of edges that did not fit
    the static ``EBLK`` budget of their node block (0 when the budget was
    sized correctly — callers wanting exactness-no-matter-what should
    ``lax.cond`` on it and fall back to a plain scatter-add).
    """
    perm: jnp.ndarray          # (NB*EBLK,) i32 indices into messages
    perm_valid: jnp.ndarray    # (NB*EBLK, 1) f32 1=real row
    dest_local: jnp.ndarray    # (NB*EBLK, 1) i32 in-block dest, -1 for pad
    n_blocks: int
    block_n: int
    n_segments: int
    n_dropped: jnp.ndarray     # () i32

    @property
    def pad_rows(self) -> int:
        return int(self.perm.shape[0])


jax.tree_util.register_dataclass(
    DeviceSegmentPrep,
    data_fields=["perm", "perm_valid", "dest_local", "n_dropped"],
    meta_fields=["n_blocks", "block_n", "n_segments"])


def default_eblk(n_edges: int, num_segments: int,
                 block_n: int = DEFAULT_BLOCK_N, slack: float = 2.0) -> int:
    """Static EBLK budget for ``prepare_device`` from static shapes only.

    A perfectly balanced segment distribution needs ``E / NB`` rows per
    node block; ``slack`` covers skew. Lane-rounded like ``prepare()``.
    """
    nb = max(1, -(-num_segments // block_n))
    even = -(-n_edges // nb)
    eblk = int(np.ceil(even * slack))
    return max(128, -(-eblk // 128) * 128)


def prepare_device(segment_ids, num_segments: int, *,
                   block_n: int = DEFAULT_BLOCK_N,
                   eblk: Optional[int] = None) -> DeviceSegmentPrep:
    """Jittable ``prepare()``: argsort by segment id + one fixed-shape scatter.

    Mirrors the numpy packing bit-for-bit when ``eblk`` matches (stable sort,
    same pad conventions: perm 0 / valid 0 / dest -1 on pad rows). Unlike the
    numpy path, EBLK is static — edges beyond a block's budget are dropped
    and counted in ``n_dropped`` instead of growing the buffer.
    """
    segment_ids = jnp.asarray(segment_ids)
    e = segment_ids.shape[0]
    nb = max(1, -(-num_segments // block_n))
    if eblk is None:
        eblk = default_eblk(e, num_segments, block_n)
    order = jnp.argsort(segment_ids, stable=True)
    sorted_seg = segment_ids[order]
    block_of = sorted_seg // block_n                    # nondecreasing
    # rank of each row within its block's run of the sorted array
    first = jnp.searchsorted(block_of, block_of, side="left")
    rank = jnp.arange(e, dtype=first.dtype) - first
    ok = rank < eblk
    n_dropped = (e - ok.sum()).astype(jnp.int32)
    # out-of-budget rows get an out-of-bounds slot; scatter mode='drop'
    slot = jnp.where(ok, block_of * eblk + rank, nb * eblk)
    perm = jnp.zeros((nb * eblk,), jnp.int32).at[slot].set(
        order.astype(jnp.int32), mode="drop")
    valid = jnp.zeros((nb * eblk, 1), jnp.float32).at[slot, 0].set(
        1.0, mode="drop")
    dest = jnp.full((nb * eblk, 1), -1, jnp.int32).at[slot, 0].set(
        (sorted_seg - block_of * block_n).astype(jnp.int32), mode="drop")
    return DeviceSegmentPrep(perm=perm, perm_valid=valid, dest_local=dest,
                             n_blocks=nb, block_n=block_n,
                             n_segments=num_segments, n_dropped=n_dropped)


def sort_by_segment(segment_ids):
    """Stable device argsort of edge->segment ids; run ONCE per graph.

    Returns ``(order, sorted_ids)`` for :func:`segment_sum_sorted`.
    """
    segment_ids = jnp.asarray(segment_ids)
    order = jnp.argsort(segment_ids, stable=True)
    return order, segment_ids[order]


def segment_sum_sorted(messages, order, sorted_ids, num_segments: int):
    """Scatter-add over receiver-sorted messages.

    ``indices_are_sorted=True`` lets XLA lower the scatter as a sorted
    segment reduction (linear merge) instead of random-access updates — the
    fast fully-jittable aggregation path on both CPU and TPU. Per layer this
    is one gather (permutation) + the sorted reduce; the argsort amortizes
    across message-passing layers via :func:`sort_by_segment`.
    """
    return jax.ops.segment_sum(messages[order], sorted_ids,
                               num_segments=num_segments,
                               indices_are_sorted=True)


def segment_sum_prepared(prep: Union[SegmentPrep, DeviceSegmentPrep],
                         messages, *,
                         block_d: int = DEFAULT_BLOCK_D):
    """messages: (E, D) -> (n_segments, D) scatter-add via the Pallas kernel.

    Accepts either preparer's output: host ``prepare()`` (numpy arrays) or
    jittable ``prepare_device()`` (traced arrays, same field layout).
    """
    d = messages.shape[-1]
    pad_d = -(-d // 128) * 128 if d % 128 else d
    gathered = messages[jnp.asarray(prep.perm)]
    gathered = gathered * jnp.asarray(prep.perm_valid, gathered.dtype)
    if pad_d != d:
        gathered = jnp.pad(gathered, ((0, 0), (0, pad_d - d)))
    out = segment_agg_call(gathered, jnp.asarray(prep.dest_local),
                           prep.n_blocks, block_n=prep.block_n,
                           block_d=min(block_d, pad_d))
    return out[: prep.n_segments, :d]


def segment_sum(messages, segment_ids, num_segments: int):
    """Convenience one-shot API (does numpy prep; not jit-friendly —
    use prepare()/segment_sum_prepared() inside training loops)."""
    prep = prepare(np.asarray(segment_ids), num_segments)
    return segment_sum_prepared(prep, messages)
