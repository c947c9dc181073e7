"""Sharded paper-scale serving: per-device graph build + halo-ring inference.

The paper's scalability claim (SIII-A) — partitions with L-hop halos make
multi-device execution *exactly* equivalent to full-graph execution — applied
to the serving path. One large request (paper-scale: ~2M points) is split by
recursive coordinate bisection (``core.partitioning``) into one shard per
device; each shard carries its owned points plus a halo ring, and a
``shard_map``-wrapped copy of the single-device pipeline builds the shard's
multi-scale hash-grid graph *on-device* and runs MeshGraphNet over it. The
prediction is masked to owned nodes and gathered back into one cloud — no
collective runs at all; the halos make each device's program self-contained,
exactly as they do for training (mirroring Barwey et al., arXiv:2410.01657,
for consistent distributed mesh-GNN inference).

Why the halo ring is ``halo_hops + 1`` nodes deep
-------------------------------------------------
Training partitions (``core.halo``) carry nodes to hop ``h`` because their
edges are *selected from the global edge list*. Here each device *rebuilds*
its graph from points, so a node's local kNN list is trustworthy only when
all of its true neighbors are present locally. Every kept edge decision
(kNN membership, symmetric closure, cross-level dedup) involves the lists of
its two endpoints, and kept edges reach endpoints at hop ``h``; their
neighbors live at hop ``h + 1``. Carrying that one extra ring of *nodes*
(never used as senders or receivers, only as kNN candidates) makes every
kept-edge decision match the full graph bit-for-bit. Edges are then masked
to ``hop(receiver) <= h - 1`` and ``hop(sender) <= h`` — the same rule as
``core.halo.build_partition`` — and the usual induction gives exact owned
outputs for ``h >= n_mp_layers`` (asserted to 1e-5 in
``tests/test_sharded_serving.py``, including the ``h = L - 1`` failure case).

Two planners produce the identical device-side layout:

* ``method='graph'``: the true hop sets, via the host multi-scale edge list
  and ``core.halo`` (exact; used by tests and moderate sizes);
* ``method='geometric'``: no graph at all — every multi-scale edge is at
  most ``halo_width`` long (the calibrated grid-cell width bounds the k-th
  neighbor distance), so dilating the owned RCB box by ``t * halo_width``
  bounds hop ``t`` from below. The resulting memberships are supersets of
  the true rings, which preserves exactness, and planning stays O(n log n)
  numpy with no cKDTree — the per-request serving path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.core import halo as halo_lib
from repro.core import partitioning
from repro.graphx import hashgrid
from repro.graphx.multiscale import MultiscaleSpec, multiscale_edges
from repro.graphx.pipeline import (make_featurizer, make_graph_forward,
                                   make_step_fn)

_BATCH_KEYS = ("points", "normals", "level_counts", "recv_ok", "send_ok",
               "owned")
_EPS = 1e-5


@dataclass(frozen=True)
class ShardSpec:
    """Static signature of a sharded inference program.

    ``ms`` is the *per-shard* multi-scale spec: its level sizes are padded
    caps on how many of each global level's points one shard may carry, and
    its grids are calibrated over shard-local clouds (a shard's extent — and
    hence its cell widths — differs from the full cloud's).

    ``halo_width`` is the calibrated geometric halo dilation (see
    :func:`global_halo_width`) frozen alongside the shapes, so per-request
    geometric planning against this spec never touches the full point cloud
    — the width is a property of the calibration reference, exactly like the
    grid resolutions it is derived from. ``0.0`` means "not calibrated"
    (graph-method specs, or specs built before the width was recorded).
    """
    n_shards: int
    halo_hops: int
    ms: MultiscaleSpec
    halo_width: float = 0.0

    @property
    def n_points(self) -> int:
        return self.ms.n_points

    def signature(self) -> tuple:
        """Hashable identity of the compiled sharded program this spec
        produces: shard/halo topology + every static shape and grid knob.
        Two specs with equal signatures compile to interchangeable programs,
        which makes ``(bucket_size, signature)`` the honest key into the
        serving LRU compiled-program cache."""
        return (self.n_shards, self.halo_hops, float(self.halo_width),
                tuple(self.ms.level_sizes), self.ms.k,
                tuple((tuple(g.resolution), g.neigh_cap, g.layout)
                      for g in self.ms.grids))


@dataclass
class ShardPlan:
    """One request's host-side plan: padded per-shard buffers + bookkeeping."""
    spec: ShardSpec
    global_ids: np.ndarray     # (P, Nmax) int64, padding slots masked
    hop: np.ndarray            # (P, Nmax) int32, padding = large sentinel
    owned: np.ndarray          # (P, Nmax) bool
    level_counts: np.ndarray   # (P, L) int32 per-level local valid counts
    points: np.ndarray         # (P, Nmax, 3) float32
    normals: np.ndarray        # (P, Nmax, 3) float32
    n_global: int

    def batch(self) -> dict:
        """The (P, ...) arrays consumed by ``make_sharded_infer_fn``."""
        h = self.spec.halo_hops
        return {
            "points": jnp.asarray(self.points),
            "normals": jnp.asarray(self.normals),
            "level_counts": jnp.asarray(self.level_counts),
            "recv_ok": jnp.asarray(self.hop <= h - 1),
            "send_ok": jnp.asarray(self.hop <= h),
            "owned": jnp.asarray(self.owned),
        }

    def gather(self, shard_out) -> np.ndarray:
        """Scatter owned rows of (P, Nmax, F) back into one (n, F) cloud.

        One masked scatter over all shards at once: ownership is a
        partition of the global ids, so the flattened owned indices never
        collide and numpy fancy-index assignment is exact.
        """
        shard_out = np.asarray(shard_out)
        out = np.zeros((self.n_global,) + shard_out.shape[2:],
                       shard_out.dtype)
        m = self.owned
        out[self.global_ids[m]] = shard_out[m]
        return out

    def scatter(self, values) -> np.ndarray:
        """Spread a global (n, F) array onto the (P, Nmax, F) shard layout.

        The inverse of :meth:`gather`, except every shard-local row with a
        real global id — owned AND halo — receives its global value, which
        is exactly what a sharded rollout step needs when the field state
        feeds back into the node features: halo rows must carry their
        owners' current state for the masked message passing to reproduce
        the unsharded step. Padding rows (no global id) are zeroed.
        """
        values = np.asarray(values)
        out = values[self.global_ids]
        out[self.hop > self.spec.halo_hops] = 0
        return out


@dataclass
class PackPlan:
    """Several geometries packed into ONE padded sharded program call.

    Cross-request packing: ``width`` is the program's static geometry (pack)
    axis; each packed geometry keeps its own :class:`ShardPlan`. The pack
    axis itself is the segment id — geometry ``g``'s points, grids, edge
    masks and normalizer encode/decode all live in lane ``g`` of a
    ``jax.vmap`` inside the sharded program, so edges can never cross
    geometries and per-geometry outputs are bitwise independent of their
    lane neighbors. Fewer than ``width`` geometries replay the last real
    plan into the padding lanes (static shapes; the compute is discarded).

    ``batch()`` stacks each plan's device arrays to ``(P, G, Nmax, ...)``;
    ``gather(out)`` de-interleaves ``(P, G, Nmax, F)`` device output back
    into one owned-node ``(n, F)`` cloud per real geometry, in pack order.
    """
    plans: Sequence[ShardPlan]
    width: int

    def __post_init__(self):
        if not self.plans:
            raise ValueError("PackPlan needs at least one ShardPlan")
        if len(self.plans) > self.width:
            raise ValueError(f"{len(self.plans)} plans exceed pack width "
                             f"{self.width}")
        sig = self.plans[0].spec.signature()
        for p in self.plans[1:]:
            if p.spec.signature() != sig:
                raise ValueError("packed plans must share one ShardSpec "
                                 "(one compiled program)")

    @property
    def spec(self) -> ShardSpec:
        return self.plans[0].spec

    def batch(self) -> dict:
        """The (P, G, ...) arrays consumed by the ``pack_width > 1``
        program of :func:`make_sharded_infer_fn`."""
        per = [p.batch() for p in self.plans]
        per += [per[-1]] * (self.width - len(per))   # replay padding lanes
        return {k: jnp.stack([b[k] for b in per], axis=1)
                for k in _BATCH_KEYS}

    def gather(self, shard_out) -> list:
        """Per-geometry owned-node clouds from (P, G, Nmax, F) output."""
        shard_out = np.asarray(shard_out)
        return [plan.gather(shard_out[:, g])
                for g, plan in enumerate(self.plans)]


def pack_plans(plans: Sequence[ShardPlan], width: int) -> PackPlan:
    """Pack same-spec shard plans into one :class:`PackPlan` of ``width``."""
    return PackPlan(plans=list(plans), width=int(width))


# ------------------------------------------------------------------ planning

def global_halo_width(points: np.ndarray, ms: MultiscaleSpec) -> float:
    """Upper bound on any edge length the device grid kNN can produce.

    Per level: when the grid is in its exact regime — the k-th-neighbor
    distance fits the narrowest cell width (``hashgrid.max_knn_cell_ratio
    <= 1``) — every emitted edge is a true kNN edge bounded by that width.
    Sparse or anisotropic levels (a 16-point coarse level of a car surface)
    can be uncalibratable to that regime; there the 27-cell search stencil
    is the only honest bound: a returned neighbor lies within two cells per
    axis, i.e. ``2 * ||cell_widths||``. Using the cell width alone in that
    regime under-bounds real edges and geometric halos silently miss
    neighbors (observed as ~1e-5 owned-node drift at 64-point buckets).

    Runs one cKDTree query per level (host planning path, never per
    dispatch: serving freezes the result into ``ShardSpec.halo_width`` at
    calibration time).
    """
    from scipy.spatial import cKDTree
    pts = np.asarray(points, np.float32)
    width = 0.0
    for n_l, g in zip(ms.level_sizes, ms.grids):
        lvl = pts[: min(n_l, len(pts))]
        extent = np.maximum(lvl.max(0) - lvl.min(0), 1e-6)
        w = extent / np.asarray(g.resolution)
        kth = float(cKDTree(lvl).query(
            lvl, k=min(g.k + 1, len(lvl)))[0][:, -1].max())
        if kth <= w.min():
            width = max(width, float(w.min()))
        else:
            width = max(width, float(2.0 * np.linalg.norm(w)))
    return width


def _membership_from_graph(points: np.ndarray, labels: np.ndarray,
                           n_shards: int, level_sizes: Sequence[int],
                           k: int, ring_hops: int) -> dict:
    """True hop rings from the host multi-scale edge list + ``core.halo``."""
    from repro.core.multiscale import multiscale_edges as host_multiscale
    s, r, _ = host_multiscale(points, list(level_sizes), k)
    parts = halo_lib.build_partitions(s, r, labels, n_shards,
                                      halo_hops=ring_hops)
    return halo_lib.export_point_shards(parts)


def _membership_geometric(points: np.ndarray, labels: np.ndarray,
                          n_shards: int, ring_hops: int,
                          halo_width: float) -> dict:
    """Hop lower bounds from RCB-box dilation by ``halo_width`` per hop."""
    pts = np.asarray(points, np.float32)
    w = max(float(halo_width), 1e-12)
    ids, hops, owned = [], [], []
    for p in range(n_shards):
        own = labels == p
        if not own.any():
            ids.append(np.zeros(0, np.int64))
            hops.append(np.zeros(0, np.int32))
            owned.append(np.zeros(0, bool))
            continue
        lo, hi = pts[own].min(0), pts[own].max(0)
        d = np.maximum(np.maximum(lo - pts, pts - hi), 0.0).max(axis=1)
        ghop = np.ceil(d / w - _EPS).astype(np.int32)
        ghop[own] = 0
        member = np.where(ghop <= ring_hops)[0]
        ids.append(member.astype(np.int64))            # already sorted
        hops.append(ghop[member])
        owned.append(own[member])
    return halo_lib.pack_point_shards(ids, hops, owned)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _merge_calibrate(clouds: Sequence[np.ndarray], k: int, n_points: int,
                     layout: str = "csr", cell_safety: float = 1.3,
                     occupancy_safety: float = 1.5) -> hashgrid.GridSpec:
    """One GridSpec that is exact for *every* shard's local cloud.

    Per-shard calibration yields per-shard resolutions; the elementwise
    minimum (widest cells) keeps the one-cell kNN window valid for all of
    them, and the capacity is the worst observed neighborhood occupancy at
    that shared resolution.
    """
    usable = [np.asarray(c, np.float32) for c in clouds if len(c) > 1]
    if not usable:
        return hashgrid.auto_spec(n_points, k, layout=layout)
    specs = [hashgrid.calibrate_spec(c, k, n_points=n_points,
                                     cell_safety=cell_safety, layout=layout)
             for c in usable]
    res = tuple(min(s.resolution[a] for s in specs) for a in range(3))
    occ = max(int(hashgrid.neighborhood_counts(c, res).max())
              for c in usable)
    cap = _round_up(max(int(np.ceil(occ * occupancy_safety)), 2 * k + 2), 128)
    return hashgrid.GridSpec(n_points=n_points, k=k, resolution=res,
                             neigh_cap=min(cap, n_points), layout=layout)


def build_shard_spec(membership: dict, points: np.ndarray,
                     level_sizes: Sequence[int], k: int, n_shards: int,
                     halo_hops: int, *, pad_factor: float = 1.0,
                     grid_layout: str = "csr",
                     halo_width: float = 0.0) -> ShardSpec:
    """Freeze static shapes + local grids from a planned membership.

    ``pad_factor`` > 1 leaves headroom so statistically similar requests
    (the serving-bucket assumption) fit the same compiled program.
    """
    pts = np.asarray(points, np.float32)
    ids = membership["global_ids"]
    mask = membership["node_mask"]
    caps, grids = [], []
    for n_l in level_sizes:
        counts = ((ids < n_l) & mask).sum(axis=1)
        cap = max(int(counts.max()), 1)
        cap = min(_round_up(int(np.ceil(cap * pad_factor)), 8), n_l)
        caps.append(cap)
        clouds = [pts[ids[p][(ids[p] < n_l) & mask[p]]]
                  for p in range(ids.shape[0])]
        grids.append(_merge_calibrate(clouds, k, cap, layout=grid_layout))
    # caps are nondecreasing by nestedness; enforce against rounding quirks
    for i in range(1, len(caps)):
        if caps[i] < caps[i - 1]:
            caps[i] = caps[i - 1]
            grids[i] = hashgrid.GridSpec(
                n_points=caps[i], k=k, resolution=grids[i].resolution,
                neigh_cap=min(grids[i].neigh_cap, caps[i]),
                layout=grids[i].layout)
    ms = MultiscaleSpec(level_sizes=tuple(caps), k=k, grids=tuple(grids))
    return ShardSpec(n_shards=n_shards, halo_hops=halo_hops, ms=ms,
                     halo_width=float(halo_width))


def plan_shards(points: np.ndarray, normals: np.ndarray, n_shards: int,
                halo_hops: int, level_sizes: Sequence[int], k: int, *,
                method: str = "graph", halo_width: Optional[float] = None,
                labels: Optional[np.ndarray] = None,
                spec: Optional[ShardSpec] = None,
                pad_factor: float = 1.0,
                grid_layout: str = "csr") -> ShardPlan:
    """Plan one request's sharded execution (host-side, cheap numpy).

    points/normals: (n, 3) with n == level_sizes[-1] (the nested-prefix
    cloud the single-device pipeline would consume). With ``spec`` given the
    plan is padded to its frozen shapes and raises ``ValueError`` when any
    shard exceeds them (the serving rejection path); otherwise a fresh
    ``ShardSpec`` is calibrated from this very request. Under
    ``method='geometric'`` a spec that carries a calibrated
    ``spec.halo_width`` supplies the dilation width by default, so planning
    a request against a frozen spec is pure RCB + box arithmetic — no pass
    over the cloud to re-derive the width.
    """
    pts = np.asarray(points, np.float32)
    n = len(pts)
    if n != level_sizes[-1]:
        raise ValueError(f"points ({n}) must match finest level "
                         f"({level_sizes[-1]})")
    if halo_hops < 1:
        raise ValueError("halo_hops must be >= 1")
    if labels is None:
        labels = partitioning.partition_rcb(pts.astype(np.float64), n_shards)
    ring = halo_hops + 1
    if method == "graph":
        mem = _membership_from_graph(pts, labels, n_shards, level_sizes, k,
                                     ring)
    elif method == "geometric":
        if halo_width is None and spec is not None and spec.halo_width > 0:
            halo_width = spec.halo_width
        if halo_width is None:
            raise ValueError("method='geometric' needs halo_width (see "
                             "global_halo_width)")
        mem = _membership_geometric(pts, labels, n_shards, ring, halo_width)
    else:
        raise ValueError(f"unknown method {method!r}")

    own_total = int(mem["owned"].sum())
    if own_total != n:
        raise AssertionError(f"ownership not a partition: {own_total} != {n}")

    if spec is None:
        spec = build_shard_spec(mem, pts, level_sizes, k, n_shards,
                                halo_hops, pad_factor=pad_factor,
                                grid_layout=grid_layout,
                                halo_width=halo_width or 0.0)
    elif spec.n_shards != n_shards or spec.halo_hops != halo_hops:
        raise ValueError("spec does not match requested shards/halo")

    nmax = spec.n_points
    ids, mask = mem["global_ids"], mem["node_mask"]
    level_counts = np.stack([((ids < n_l) & mask).sum(axis=1)
                             for n_l in level_sizes], axis=1).astype(np.int32)
    for lvl, cap in enumerate(spec.ms.level_sizes):
        over = level_counts[:, lvl] > cap
        if over.any():
            raise ValueError(
                f"shard capacity exceeded at level {lvl}: "
                f"{int(level_counts[over, lvl].max())} > cap {cap} "
                "(recalibrate the ShardSpec or raise pad_factor)")

    nrm = np.asarray(normals, np.float32)
    P_ = n_shards
    out = {
        "global_ids": np.zeros((P_, nmax), np.int64),
        "hop": np.full((P_, nmax), halo_lib.HOP_PAD, np.int32),
        "owned": np.zeros((P_, nmax), bool),
        "points": np.zeros((P_, nmax, 3), np.float32),
        "normals": np.zeros((P_, nmax, 3), np.float32),
    }
    for p in range(P_):
        m = int(mem["n_local"][p])
        sel = ids[p, :m]
        out["global_ids"][p, :m] = sel
        out["hop"][p, :m] = mem["hop"][p, :m]
        out["owned"][p, :m] = mem["owned"][p, :m]
        out["points"][p, :m] = pts[sel]
        out["normals"][p, :m] = nrm[sel]
    return ShardPlan(spec=spec, global_ids=out["global_ids"],
                     hop=out["hop"], owned=out["owned"],
                     level_counts=level_counts, points=out["points"],
                     normals=out["normals"], n_global=n)


def shard_spec_for(bucket_size: int, n_shards: int, halo_hops: int,
                   pad_factor: float, *, reference_points: np.ndarray,
                   reference_normals: np.ndarray,
                   level_sizes: Sequence[int], k: int,
                   ms: Optional[MultiscaleSpec] = None,
                   method: str = "geometric",
                   grid_layout: str = "csr") -> ShardSpec:
    """Derive the frozen sharded-program parameters for ONE bucket size.

    The bucketized-ShardSpec entry point: per-shard level capacities,
    merged shard-local grids and the geometric halo width all come from a
    reference cloud at the bucket's resolution — a ``ShardSpec`` is a
    function of ``(bucket_size, n_shards, halo_hops, pad_factor)`` plus the
    calibration reference, never an init-time constant. Deterministic for a
    fixed reference, so every rebuild of a bucket (LRU evict→rebuild,
    restart from a deploy artifact) reproduces the identical
    :meth:`ShardSpec.signature` and therefore the identical compiled
    program.

    ``ms`` is the bucket's *global* multi-scale spec, used only to bound
    the halo width (:func:`global_halo_width`); when omitted it is
    calibrated from the reference prefix levels.
    """
    pts = np.asarray(reference_points, np.float32)
    if len(pts) != int(bucket_size) or level_sizes[-1] != int(bucket_size):
        raise ValueError(
            f"reference cloud ({len(pts)}) and finest level "
            f"({level_sizes[-1]}) must both equal bucket_size "
            f"({bucket_size})")
    if ms is None:
        grids = tuple(hashgrid.calibrate_spec(pts[:m], k, n_points=m)
                      for m in level_sizes)
        ms = MultiscaleSpec(level_sizes=tuple(level_sizes), k=k, grids=grids)
    width = global_halo_width(pts, ms) if method == "geometric" else None
    plan = plan_shards(pts, reference_normals, n_shards, halo_hops,
                       level_sizes, k, method=method, halo_width=width,
                       pad_factor=pad_factor, grid_layout=grid_layout)
    return plan.spec


# ----------------------------------------------------------------- execution

def make_sharded_infer_fn(cfg: GNNConfig, sspec: ShardSpec, mesh, *,
                          axis: str = "data", knn_impl: str = "xla",
                          norm_in=None, norm_out=None,
                          jit: bool = True, pack_width: int = 1):
    """Build ``infer(params, batch) -> (P[, G], Nmax, node_out)`` under
    shard_map.

    With ``pack_width == 1`` (the default), ``batch`` is
    ``ShardPlan.batch()``: each device receives its own (1, Nmax, ...)
    block, builds its shard's multi-scale graph with the shard-local grids,
    masks edges to the halo rule, and runs the *same*
    ``make_graph_forward`` as the single-device pipeline. No collectives:
    the halos already make every shard self-contained; the gather back to
    one cloud is ``ShardPlan.gather``.

    With ``pack_width > 1`` (cross-request packing), ``batch`` is
    ``PackPlan.batch()`` — (P, G, Nmax, ...) arrays — and the per-shard
    body vmaps over the geometry (pack) axis G. The pack lane is the
    segment id: every lane builds its own graph from its own points and
    grids, so no edge, aggregation or normalizer statistic can cross
    geometries; outputs per lane equal the ``pack_width == 1`` program run
    solo on that geometry. The output grows a matching G axis, consumed by
    ``PackPlan.gather``.
    """
    forward = make_graph_forward(cfg, norm_in=norm_in, norm_out=norm_out)
    ms = sspec.ms
    pack_width = int(pack_width)

    def one(params, b):
        """One geometry lane on one shard: (Nmax, ...) -> (Nmax, out)."""
        pts = b["points"].astype(jnp.float32)
        s, r, em = multiscale_edges(pts, b["level_counts"], ms,
                                    impl=knn_impl)
        em = em & b["send_ok"][s] & b["recv_ok"][r]
        s = jnp.where(em, s, 0)
        r = jnp.where(em, r, 0)
        pred = forward(params, pts, b["normals"], s, r, em)
        return pred * b["owned"][:, None].astype(pred.dtype)

    def local(params, batch):
        b = {k: v[0] for k, v in batch.items()}   # strip the shard axis
        if pack_width > 1:
            out = jax.vmap(lambda bg: one(params, bg))(b)
        else:
            out = one(params, b)
        return out[None]

    in_specs = (P(), {k: P(axis) for k in _BATCH_KEYS})
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(axis))
    return jax.jit(fn) if jit else fn


def make_sharded_rollout_fn(cfg: GNNConfig, sspec: ShardSpec, mesh, *,
                            steps: int, axis: str = "data",
                            knn_impl: str = "xla",
                            norm_in=None, norm_out=None, jit: bool = True,
                            pack_width: int = 1):
    """Sharded generate: graph-once, step-``steps`` under shard_map.

    Returns ``gen(params, batch, state, remaining) -> (state', remaining')``
    where ``batch`` is ``ShardPlan.batch()`` / ``PackPlan.batch()`` arrays
    (rollout lanes ride the pack axis G), ``state`` is
    ``(P[, G], Nmax, node_out)`` in the shard-local layout produced by
    ``ShardPlan.scatter``, and ``remaining`` is ``(P[, G])`` int32 (every
    shard carries the same per-lane count). Each shard builds its halo'd
    graph and featurizes ONCE, then scans the physics step ``steps`` times
    — still zero collectives. Returned state is masked to owned rows.

    Exactness across flushes: with ``rollout_state_feats=False`` the state
    never re-enters message passing, so any ``steps`` per call reproduces
    the unsharded scan on owned rows. With state feedback the halo rings
    only cover ONE exact step — the rollout engine then clamps to
    ``steps=1`` and re-scatters the gathered global state between flushes
    (a host-side halo exchange).
    """
    featurize = make_featurizer(cfg, norm_in=norm_in)
    step = make_step_fn(cfg, norm_out=norm_out)
    ms = sspec.ms
    pack_width = int(pack_width)

    def one(params, b, state, remaining):
        pts = b["points"].astype(jnp.float32)
        s, r, em = multiscale_edges(pts, b["level_counts"], ms,
                                    impl=knn_impl)
        em = em & b["send_ok"][s] & b["recv_ok"][r]
        s = jnp.where(em, s, 0)
        r = jnp.where(em, r, 0)
        graph = featurize(pts, b["normals"], s, r, em)

        def body(carry, _):
            st, rem = carry
            with jax.named_scope("rollout/step"):
                nxt = step(params, graph, st)
            st = jnp.where(rem > 0, nxt, st)
            rem = jnp.maximum(rem - 1, 0)
            return (st, rem), None

        (state, remaining), _ = jax.lax.scan(
            body, (state, remaining), None, length=steps)
        return state * b["owned"][:, None].astype(state.dtype), remaining

    def local(params, batch, state, remaining):
        b = {k: v[0] for k, v in batch.items()}   # strip the shard axis
        st, rem = state[0], remaining[0]
        if pack_width > 1:
            out, rem2 = jax.vmap(
                lambda bg, sg, rg: one(params, bg, sg, rg))(b, st, rem)
        else:
            out, rem2 = one(params, b, st, rem)
        return out[None], rem2[None]

    in_specs = (P(), {k: P(axis) for k in _BATCH_KEYS}, P(axis), P(axis))
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(axis), P(axis)))
    return jax.jit(fn) if jit else fn
