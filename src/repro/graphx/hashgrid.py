"""Device-resident hash-grid (cell-list) k-nearest-neighbor search.

Replaces the host-side ``scipy.spatial.cKDTree`` in the serving hot path
(paper SIII-B: graphs are built directly from sampled geometry — this module
makes that construction jittable so it runs on the accelerator, fused with
the model forward pass).

Everything is fixed-shape: for a static ``GridSpec`` the whole search
compiles once per (n_points, k, resolution, neigh_cap) signature and is
reused across requests. Points are bucketed into a per-axis-resolved uniform
grid over their bounding box (anisotropic resolution keeps cells cube-ish on
elongated bodies like cars). Construction then builds a *compacted
neighborhood table*: for every cell, the ids of all points in its 27
surrounding cells, written by one scatter from the (point, offset) side —
so the candidate width is the actual neighborhood occupancy cap, not
27 x per-cell capacity. Each query reads its own cell's row and keeps the
k nearest via ``repro.kernels.knn`` (Pallas kernel or XLA reference).

Exactness: the search is exact whenever every point's k-th neighbor lies
within one cell width on every axis and no cell neighborhood overflows
``neigh_cap``. ``calibrate_spec`` picks such a spec from a reference cloud
at setup time (one host cKDTree query — never in the hot path);
``overflow_count`` and ``max_knn_cell_ratio`` are the matching diagnostics.

Layouts: the default ``layout='csr'`` never materializes anything over the
grid — points are sorted by cell id once and each query's candidate row is
assembled by 27 binary searches into that order (an occupied-cell CSR view),
so memory is O(n_points * neigh_cap) regardless of resolution and
paper-scale 2M-point buckets are constructible on one host. The original
``layout='dense'`` per-cell neighborhood table is kept as a reference
implementation (its memory is O(n_cells * neigh_cap), so ``calibrate_spec``
bounds its cell count at ``cell_budget * n_points``); both layouts produce
identical neighbor sets, which the tests enforce.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.knn import ops as knn_ops

# CSR cell ids must stay addressable in int32 (jax default int). The grid is
# purely arithmetic under CSR — no O(n_cells) array exists — so this is the
# only resolution bound.
_MAX_INT32_CELLS = 2 ** 31 - 64

_OFFSETS = np.array([(dx, dy, dz)
                     for dx in (-1, 0, 1)
                     for dy in (-1, 0, 1)
                     for dz in (-1, 0, 1)], np.int32)        # (27, 3)


@dataclass(frozen=True)
class GridSpec:
    """Static shape signature of one hash-grid kNN search."""
    n_points: int                     # padded point-buffer length
    k: int                            # neighbors per query
    resolution: Tuple[int, int, int]  # cells per axis (rx, ry, rz)
    neigh_cap: int                    # candidate capacity per cell nbhd (C)
    layout: str = "csr"               # 'csr' (occupied-cell) | 'dense' table

    @property
    def n_cells(self) -> int:
        rx, ry, rz = self.resolution
        return rx * ry * rz

    @property
    def n_candidates(self) -> int:
        return self.neigh_cap


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def auto_spec(n_points: int, k: int = 6, mode: str = "surface",
              resolution: int | Tuple[int, int, int] | None = None,
              neigh_cap: int | None = None, layout: str = "csr") -> GridSpec:
    """Heuristic spec for roughly isotropic uniform point clouds.

    ``mode='surface'``: points on a 2-manifold — occupied cells scale like
    R^2, so R ~ sqrt(n/k)/2 keeps the cell width above the k-th-neighbor
    distance with headroom. ``mode='volume'``: R ~ (n/k)^(1/3).

    For real geometries prefer ``calibrate_spec`` (measures the cloud).
    """
    if resolution is None:
        if mode == "surface":
            r = int(round(math.sqrt(n_points / max(k, 1)) / 2))
        else:
            r = int(round((n_points / max(k, 1)) ** (1.0 / 3.0)))
        resolution = max(2, min(r, 128))
    if isinstance(resolution, int):
        resolution = (resolution,) * 3
    if neigh_cap is None:
        rx, ry, rz = resolution
        if mode == "surface":
            est = n_points / max(rx * ry, 1)   # occupied cells ~ one face
        else:
            est = n_points / max(rx * ry * rz, 1)
        # a 3x3x3 neighborhood crosses the surface in ~9 occupied cells
        occ_cells = 9 if mode == "surface" else 27
        neigh_cap = _round_up(max(4 * k, int(math.ceil(3 * occ_cells * est))),
                              128)
        neigh_cap = min(neigh_cap, n_points)
    return GridSpec(n_points=n_points, k=k, resolution=tuple(resolution),
                    neigh_cap=neigh_cap, layout=layout)


def calibrate_spec(points: np.ndarray, k: int, n_points: int | None = None,
                   cell_safety: float = 1.3,
                   occupancy_safety: float = 1.5,
                   cell_budget: float = 8.0, layout: str = "csr") -> GridSpec:
    """Measure a reference cloud and return an exact-by-construction spec.

    Host-side, setup-time only (one cKDTree query). The cell size is set to
    ``cell_safety`` times the largest k-th-neighbor distance, so the 27-cell
    window provably covers every true neighborhood of the reference cloud
    (and, with the safety margins, of statistically similar clouds — e.g.
    other geometries sampled at the same resolution in a serving bucket).
    """
    from scipy.spatial import cKDTree
    pts = np.asarray(points, np.float32)
    n = len(pts)
    dist, _ = cKDTree(pts).query(pts, k=min(k + 1, n))
    kth = float(dist[:, -1].max())
    extent = np.maximum(pts.max(0) - pts.min(0), 1e-6)
    cell = max(kth * cell_safety, 1e-6)
    res = tuple(int(max(1, math.floor(e / cell))) for e in extent)
    # dense: the table is O(n_cells), so bound total cells by
    # cell_budget * n. csr: nothing is materialized over the grid; only the
    # int32 cell-id range bounds the resolution. Growing the cells only
    # loosens the kNN window (exactness is preserved), at the price of a
    # larger neigh_cap.
    n_cells = res[0] * res[1] * res[2]
    max_cells = (max(int(cell_budget * n), 27) if layout == "dense"
                 else _MAX_INT32_CELLS)
    if n_cells > max_cells:
        shrink = (max_cells / n_cells) ** (1.0 / 3.0)
        res = tuple(int(max(1, math.floor(r * shrink))) for r in res)
    occ = int(neighborhood_counts(pts, res).max())
    cap = _round_up(max(int(math.ceil(occ * occupancy_safety)), 2 * k + 2),
                    128)
    return GridSpec(n_points=n_points or n, k=k, resolution=res,
                    neigh_cap=min(cap, n_points or n), layout=layout)


def _cells(points, valid, spec: GridSpec):
    """Per-point integer cell coords + flat cell ids (n_cells = sentinel)."""
    res = jnp.asarray(spec.resolution, jnp.int32)
    big = jnp.float32(3.4e38)
    pts = points.astype(jnp.float32)
    v = valid[:, None]
    lo = jnp.min(jnp.where(v, pts, big), axis=0)
    hi = jnp.max(jnp.where(v, pts, -big), axis=0)
    extent = jnp.maximum(hi - lo, 1e-6)
    cc = jnp.floor((pts - lo) / extent * res).astype(jnp.int32)
    cc = jnp.clip(cc, 0, res - 1)
    cid = _flat_cid(cc, spec)
    cid = jnp.where(valid, cid, spec.n_cells)
    return cc, cid


def _flat_cid(cc, spec: GridSpec):
    _, ry, rz = spec.resolution
    return (cc[..., 0] * ry + cc[..., 1]) * rz + cc[..., 2]


def build_table(points, n_valid, spec: GridSpec):
    """Compacted neighborhood table: (n_cells, neigh_cap) point ids, -1 empty.

    One stable sort by cell id orders points; per-(cell, offset) exclusive
    prefix sums assign each point a slot in the neighborhood rows of its 27
    surrounding cells; a single scatter (mode='drop' culls out-of-range
    neighbors, padded points, and capacity overflow) fills the table.

    Returns (table, cid (N,) per-point cell id, valid (N,) bool).
    """
    n = spec.n_points
    rx, ry, rz = spec.resolution
    res = jnp.asarray(spec.resolution, jnp.int32)
    valid = jnp.arange(n) < n_valid
    cc, cid = _cells(points, valid, spec)

    order = jnp.argsort(cid)                      # stable: sentinel rows last
    sorted_cid = cid[order]
    starts = jnp.searchsorted(sorted_cid, jnp.arange(spec.n_cells + 1))
    counts = jnp.diff(starts)                     # (n_cells,)
    rank = jnp.arange(n) - starts[jnp.clip(sorted_cid, 0, spec.n_cells - 1)]

    # per-cell neighborhood layout: slot base of offset j in cell c's row is
    # the exclusive prefix sum of the 27 neighbor-cell occupancies
    cell_ids = jnp.arange(spec.n_cells, dtype=jnp.int32)
    cell_cc = jnp.stack([cell_ids // (ry * rz),
                         (cell_ids // rz) % ry,
                         cell_ids % rz], axis=-1)             # (n_cells, 3)
    nbr_cc = cell_cc[:, None, :] + jnp.asarray(_OFFSETS)[None]
    nbr_ok = jnp.all((nbr_cc >= 0) & (nbr_cc < res), axis=-1)  # (n_cells, 27)
    nbr_cid = _flat_cid(jnp.clip(nbr_cc, 0, res - 1), spec)
    nbr_counts = jnp.where(nbr_ok, counts[nbr_cid], 0)
    base = jnp.cumsum(nbr_counts, axis=1) - nbr_counts         # (n_cells, 27)

    # scatter side: sorted point i (cell c_p, rank m) occupies slot
    # base[c', j] + m of every cell c' = c_p - offset_j it neighbors
    sorted_cc = jnp.clip(cc[order], 0, res - 1)
    home_cc = sorted_cc[:, None, :] - jnp.asarray(_OFFSETS)[None]  # (N, 27, 3)
    home_ok = jnp.all((home_cc >= 0) & (home_cc < res), axis=-1)
    home_ok &= (sorted_cid < spec.n_cells)[:, None]
    home_cid = _flat_cid(jnp.clip(home_cc, 0, res - 1), spec)
    j_ids = jnp.arange(27, dtype=jnp.int32)[None, :]
    col = base[home_cid, j_ids] + rank[:, None]
    row = jnp.where(home_ok, home_cid, spec.n_cells)    # OOB row -> dropped
    table = jnp.full((spec.n_cells, spec.neigh_cap), -1, jnp.int32)
    table = table.at[row, col].set(
        jnp.broadcast_to(order.astype(jnp.int32)[:, None], (n, 27)),
        mode="drop")
    return table, cid, valid


_XY_OFFSETS = np.array([(dx, dy) for dx in (-1, 0, 1) for dy in (-1, 0, 1)],
                       np.int32)                               # (9, 2)


def csr_candidate_lists(points, n_valid, spec: GridSpec):
    """Occupied-cell CSR candidate gather — no per-cell table at all.

    One stable sort by cell id turns the point buffer into a CSR layout
    whose row pointers are *implicit*: the slice of cell-id range [a, b] is
    ``[searchsorted(sorted_cid, a, left), searchsorted(sorted_cid, b+1,
    left))``. The flat cell id is contiguous along z, so a query's 3x3x3
    window is 9 contiguous id ranges (one per (dx, dy) column) — 18 binary
    searches per query. The 9 segment lengths are prefix-summed into a
    packed row of width ``neigh_cap``. Slot ``t`` lies in the last segment
    ``j`` whose base is at most ``t`` and reads sorted position
    ``start[j] + t - base[j]``; the bases are nondecreasing, so 9
    elementwise compare-selects against the (N, 9) tables map every slot
    (no scatter, scan or per-slot gather). Slots past ``neigh_cap`` are
    dropped — identical overflow semantics to the dense table's
    ``mode='drop'`` scatter.

    Memory: O(n_points) bookkeeping + the (N, C) candidate row that every
    layout materializes; nothing scales with ``spec.n_cells``.
    """
    n = spec.n_points
    _, _, rz = spec.resolution
    res = jnp.asarray(spec.resolution, jnp.int32)
    valid = jnp.arange(n) < n_valid
    cc, cid = _cells(points, valid, spec)

    order = jnp.argsort(cid).astype(jnp.int32)     # stable: sentinel rows last
    sorted_cid = cid[order]

    # 9 contiguous cell-id ranges per query: column (cx+dx, cy+dy), z in
    # [cz-1, cz+1] clamped to the grid
    col_cc = cc[:, None, :2] + jnp.asarray(_XY_OFFSETS)[None]  # (N, 9, 2)
    col_ok = jnp.all((col_cc >= 0) & (col_cc < res[:2]), axis=-1)
    col_cc = jnp.clip(col_cc, 0, res[:2] - 1)
    col_base = (col_cc[..., 0] * res[1] + col_cc[..., 1]) * rz  # (N, 9)
    z_lo = jnp.maximum(cc[:, 2] - 1, 0)[:, None]
    z_hi = jnp.minimum(cc[:, 2] + 1, rz - 1)[:, None]
    bounds = jnp.stack([col_base + z_lo, col_base + z_hi + 1], axis=0)
    found = jnp.searchsorted(sorted_cid, bounds.reshape(-1),
                             side="left").reshape(2, n, 9).astype(jnp.int32)
    start, end = found[0], found[1]
    cnt = jnp.where(col_ok, end - start, 0)
    base = jnp.cumsum(cnt, axis=1) - cnt                       # (N, 9) excl.
    total = base[:, -1] + cnt[:, -1]                           # (N,)

    # slot t of segment j sits at start[j] + t - base[j], and t belongs to
    # the last j with base[j] <= t (base is nondecreasing in j, and a
    # zero-length segment shares its base with the next, which overwrites
    # it): 9 elementwise compare-selects per slot, no gather, no scan
    slots = jnp.arange(spec.neigh_cap, dtype=jnp.int32)
    shift = start - base                                       # (N, 9)
    pos = slots[None, :] + shift[:, :1]
    for j in range(1, 9):
        pos = jnp.where(slots[None, :] >= base[:, j:j + 1],
                        slots[None, :] + shift[:, j:j + 1], pos)
    cand = order[jnp.clip(pos, 0, n - 1)]                      # (N, C)
    slot_ok = slots[None, :] < total[:, None]
    self_ids = jnp.arange(n, dtype=jnp.int32)[:, None]
    cand_valid = slot_ok & (cand != self_ids) & valid[:, None]
    return cand, cand_valid, valid


def candidate_lists(points, n_valid, spec: GridSpec):
    """Fixed-size per-query candidate ids (the query cell's neighborhood row).

    Returns (cand_idx (N, C) i32 safe-valued, cand_valid (N, C) bool,
    valid (N,) bool query mask)."""
    if spec.layout == "csr":
        return csr_candidate_lists(points, n_valid, spec)
    table, cid, valid = build_table(points, n_valid, spec)
    cand = table[jnp.clip(cid, 0, spec.n_cells - 1)]   # (N, C)
    self_ids = jnp.arange(spec.n_points, dtype=jnp.int32)[:, None]
    cand_valid = (cand >= 0) & (cand != self_ids) & valid[:, None]
    return jnp.maximum(cand, 0), cand_valid, valid


def knn(points, n_valid, spec: GridSpec, *, impl: str = "xla"):
    """Fixed-degree kNN: (N, 3) points -> ((N, k) idx, (N, k) d2, (N, k) mask).

    ``n_valid`` is a (traced) scalar: points[n_valid:] are padding and are
    neither queried nor returned as neighbors. Missing neighbors (sparse
    clouds, padding rows) have idx -1 and mask False.
    """
    assert points.shape[0] == spec.n_points, (points.shape, spec.n_points)
    cand_idx, cand_valid, valid = candidate_lists(points, n_valid, spec)
    cand_pos = points.astype(jnp.float32)[cand_idx]
    idx, d2, mask = knn_ops.topk_neighbors(
        points.astype(jnp.float32), cand_pos, cand_idx, cand_valid,
        spec.k, impl=impl)
    mask = mask & valid[:, None]
    idx = jnp.where(mask, idx, -1)
    return idx, d2, mask


def symmetric_edges(nbr_idx, nbr_mask) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                jnp.ndarray]:
    """Fixed-shape symmetric closure of (n, k) neighbor lists.

    Emits the forward edges (nbr -> self, one per neighbor slot) plus the
    reverse edges, masking reverse edges that duplicate an existing forward
    edge (mutual pairs) — the device equivalent of the host
    ``knn_edges(bidirectional=True)`` unique() pass, with static shape 2nk.

    Returns (senders (2nk,) i32, receivers (2nk,) i32, edge_mask (2nk,) bool);
    masked slots have senders = receivers = 0.
    """
    n, k = nbr_idx.shape
    rec = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    t = jnp.maximum(nbr_idx, 0)
    # reverse edge (i -> t) duplicates a forward edge iff i in nbr[t]
    dup = jnp.any((nbr_idx[t] == rec[:, :, None]) & nbr_mask[t], axis=-1)
    rev_mask = nbr_mask & ~dup
    senders = jnp.concatenate([nbr_idx.reshape(-1), rec.reshape(-1)])
    receivers = jnp.concatenate([rec.reshape(-1), nbr_idx.reshape(-1)])
    emask = jnp.concatenate([nbr_mask.reshape(-1), rev_mask.reshape(-1)])
    senders = jnp.where(emask, senders, 0).astype(jnp.int32)
    receivers = jnp.where(emask, receivers, 0).astype(jnp.int32)
    return senders, receivers, emask


# ---------------------------------------------------------------- diagnostics

def neighborhood_counts(pts: np.ndarray, res) -> np.ndarray:
    """3x3x3-neighborhood occupancy of every *occupied* cell.

    Occupied-cell (CSR-style) computation — O(27 n log n) host work and O(n)
    memory regardless of resolution, so the diagnostics scale to the same
    paper-scale grids the csr layout unlocks. Empty cells host no queries, so
    restricting to occupied cells loses nothing. Public because shard-spec
    calibration (``graphx.sharded._merge_calibrate``) sizes merged-grid
    capacities from the worst observed occupancy across shard clouds.
    """
    res = np.asarray(res, np.int64)
    lo, hi = pts.min(0), pts.max(0)
    extent = np.maximum(hi - lo, 1e-6)
    cc = np.clip(np.floor((pts - lo) / extent * res).astype(np.int64),
                 0, res - 1)
    cid = (cc[:, 0] * res[1] + cc[:, 1]) * res[2] + cc[:, 2]
    occ, counts = np.unique(cid, return_counts=True)
    occ_cc = np.stack([occ // (res[1] * res[2]),
                       (occ // res[2]) % res[1],
                       occ % res[2]], axis=-1)                 # (M, 3)
    nbr = occ_cc[:, None, :] + _OFFSETS[None].astype(np.int64)  # (M, 27, 3)
    ok = np.all((nbr >= 0) & (nbr < res), axis=-1)
    nbr_cid = (nbr[..., 0] * res[1] + nbr[..., 1]) * res[2] + nbr[..., 2]
    idx = np.clip(np.searchsorted(occ, nbr_cid), 0, len(occ) - 1)
    found = (occ[idx] == nbr_cid) & ok
    return np.where(found, counts[idx], 0).sum(axis=1)


#: Back-compat alias — ``neighborhood_counts`` predates its promotion.
_neighborhood_counts = neighborhood_counts


def overflow_count(points: np.ndarray, n_valid: int, spec: GridSpec) -> int:
    """Host-side: candidate slots lost to neighborhood-capacity overflow."""
    nc = neighborhood_counts(np.asarray(points)[:n_valid], spec.resolution)
    return int(np.maximum(nc - spec.neigh_cap, 0).sum())


def max_knn_cell_ratio(points: np.ndarray, n_valid: int,
                       spec: GridSpec) -> float:
    """Host-side: max over points of (k-th NN distance / narrowest cell width).

    <= 1.0 guarantees the 27-cell window contains the true kNN (exactness,
    given no overflow). Uses cKDTree — diagnostics only, never the hot path.
    """
    from scipy.spatial import cKDTree
    pts = np.asarray(points)[:n_valid]
    dist, _ = cKDTree(pts).query(pts, k=min(spec.k + 1, len(pts)))
    kth = dist[:, -1]
    widths = np.maximum(pts.max(0) - pts.min(0), 1e-6) / \
        np.asarray(spec.resolution)
    return float(kth.max() / max(widths.min(), 1e-12))
