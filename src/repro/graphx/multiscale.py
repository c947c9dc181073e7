"""Device-side multi-scale edge union over nested prefixes (paper SIII-C).

The host reference (``repro.core.multiscale.multiscale_edges``) computes kNN
per level with cKDTree and dedupes the union with ``np.unique``. Here every
level is a fixed-shape hash-grid kNN over the first ``n_l`` points, and the
cross-level dedup is a mask: a fine-level edge is disabled when the same
(sender, receiver) pair already exists at a coarser level — exactly the
host's "keep the coarsest occurrence" semantics, with static shapes
(sum over levels of 2 * n_l * k edge slots).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.graphx import hashgrid


@dataclass(frozen=True)
class MultiscaleSpec:
    """Static signature of a multi-scale device graph build."""
    level_sizes: Tuple[int, ...]          # increasing (coarse -> fine)
    k: int
    grids: Tuple[hashgrid.GridSpec, ...]  # one per level

    @property
    def n_points(self) -> int:
        return self.level_sizes[-1]

    @property
    def n_edges(self) -> int:
        return sum(2 * n * self.k for n in self.level_sizes)

    @property
    def level_of_edge(self) -> np.ndarray:
        """Static (n_edges,) level id of every edge slot."""
        return np.concatenate([np.full(2 * n * self.k, lvl, np.int32)
                               for lvl, n in enumerate(self.level_sizes)])


def auto_multiscale_spec(level_sizes: Sequence[int], k: int = 6,
                         mode: str = "surface") -> MultiscaleSpec:
    sizes = tuple(level_sizes)
    if list(sizes) != sorted(sizes):
        raise ValueError("level_sizes must be increasing (coarse -> fine)")
    grids = tuple(hashgrid.auto_spec(n, k, mode=mode) for n in sizes)
    return MultiscaleSpec(level_sizes=sizes, k=k, grids=grids)


def multiscale_edges(points, n_valid, ms: MultiscaleSpec, *,
                     impl: str = "xla"):
    """Union of per-level symmetric kNN edges with cross-level dedup masks.

    points: (n_finest, 3); n_valid: traced scalar — valid points must be a
    prefix (nested sampling already orders them that way) — or a traced
    (n_levels,) vector of independent per-level valid counts (sharded
    serving: each shard's slice of a level is its own prefix, and its length
    is not determined by the total). The cross-level dedup below is already
    driven by the per-level kNN validity masks, so dynamic level membership
    needs no further changes.
    Returns (senders (E,), receivers (E,), edge_mask (E,) bool) with
    E = ms.n_edges static; masked slots have senders = receivers = 0.
    """
    assert points.shape[0] == ms.n_points, (points.shape, ms.n_points)
    n_valid = jnp.asarray(n_valid)
    if n_valid.ndim not in (0, 1):
        raise ValueError(f"n_valid must be a scalar or (n_levels,) vector, "
                         f"got shape {n_valid.shape}")
    if n_valid.ndim == 1 and n_valid.shape[0] != len(ms.level_sizes):
        raise ValueError(f"per-level n_valid has {n_valid.shape[0]} entries "
                         f"for {len(ms.level_sizes)} levels")
    nbrs = []
    for lvl, (n_l, gspec) in enumerate(zip(ms.level_sizes, ms.grids)):
        nv = (jnp.minimum(n_valid, n_l) if n_valid.ndim == 0
              else n_valid[lvl])
        idx, _, mask = hashgrid.knn(points[:n_l], nv, gspec,
                                    impl=impl)
        nbrs.append((idx, mask))

    seg_s, seg_r, seg_m = [], [], []
    for lvl, ((idx, mask), n_l) in enumerate(zip(nbrs, ms.level_sizes)):
        s, r, em = hashgrid.symmetric_edges(idx, mask)
        for c_lvl in range(lvl):
            c_idx, c_mask = nbrs[c_lvl]
            n_c = ms.level_sizes[c_lvl]
            both = (s < n_c) & (r < n_c) & em
            sc = jnp.clip(s, 0, n_c - 1)
            rc = jnp.clip(r, 0, n_c - 1)
            # coarse edge set = symmetric closure of coarse neighbor lists:
            # (s, r) present iff s in nbr[r] or r in nbr[s]
            in_r = jnp.any((c_idx[rc] == s[:, None]) & c_mask[rc], axis=1)
            in_s = jnp.any((c_idx[sc] == r[:, None]) & c_mask[sc], axis=1)
            em = em & ~(both & (in_r | in_s))
        seg_s.append(s)
        seg_r.append(r)
        seg_m.append(em)

    senders = jnp.concatenate(seg_s)
    receivers = jnp.concatenate(seg_r)
    emask = jnp.concatenate(seg_m)
    senders = jnp.where(emask, senders, 0)
    receivers = jnp.where(emask, receivers, 0)
    return senders, receivers, emask
