"""End-to-end jitted inference: padded point cloud -> predicted fields.

One ``jax.jit``-compiled function per (MultiscaleSpec, GNNConfig) pair does
hash-grid kNN at every level, multi-scale edge union, node/edge featurization
and the MeshGraphNet forward pass — no host cKDTree, no host featurization,
no recompilation across requests of the same bucket. This is the paper's
real-time-inference promise made concrete: mesh-free graph construction in
the same XLA program as the model.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GNNConfig
from repro.graphx import features as fx
from repro.graphx.multiscale import MultiscaleSpec, multiscale_edges
from repro.models import meshgraphnet


def make_featurizer(cfg: GNNConfig, *,
                    norm_in: Optional[Tuple[np.ndarray, np.ndarray]] = None):
    """Static featurization over an already-built edge set.

    Returns ``featurize(points, normals, senders, receivers, emask)`` -> a
    graph dict ``{node_feats, edge_feats, senders, receivers, emask}``. This
    is the step-invariant half of the pipeline — everything a T-step rollout
    computes exactly once (prefill) and every physics step reuses.
    """
    in_stats = (None if norm_in is None else
                (jnp.asarray(norm_in[0], jnp.float32),
                 jnp.asarray(norm_in[1], jnp.float32)))

    def featurize(points, normals, senders, receivers, emask):
        # named_scope (not TraceAnnotation): rides into the HLO metadata so
        # a jax.profiler capture labels the compiled ops by pipeline stage
        points = points.astype(jnp.float32)
        with jax.named_scope("graphx/featurize"):
            feats = fx.node_input_features(points, normals, cfg.fourier_freqs)
            if in_stats is not None:
                feats = (feats - in_stats[0]) / in_stats[1]
            edge_feats = fx.relative_edge_features(points, senders, receivers,
                                                   emask)
        return {"node_feats": feats, "edge_feats": edge_feats,
                "senders": senders, "receivers": receivers, "emask": emask}

    return featurize


def make_step_fn(cfg: GNNConfig, *,
                 norm_out: Optional[Tuple[np.ndarray, np.ndarray]] = None):
    """One physics step over a featurized graph: ``step(params, graph,
    state)`` -> next state (N, node_out).

    The per-step half of the pipeline: model forward + output denorm +
    state integration (:func:`repro.models.meshgraphnet.step`). Single-shot
    prediction is this from a zero state with the default ``'direct'``
    integrator; the rollout engine scans it T times over the same graph.
    """
    out_stats = (None if norm_out is None else
                 (jnp.asarray(norm_out[0], jnp.float32),
                  jnp.asarray(norm_out[1], jnp.float32)))

    def step(params, graph, state):
        nf = graph["node_feats"]
        with jax.named_scope("graphx/model"):
            return meshgraphnet.step(
                params, cfg, nf, graph["edge_feats"],
                graph["senders"], graph["receivers"], state,
                edge_mask=graph["emask"].astype(nf.dtype),
                out_stats=out_stats)

    return step


def make_graph_forward(cfg: GNNConfig, *,
                       norm_in: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                       norm_out: Optional[Tuple[np.ndarray, np.ndarray]] = None):
    """Featurize + model forward over an already-built edge set.

    Returns ``forward(params, points, normals, senders, receivers, emask)``
    -> (N, node_out). The single-device pipeline and the shard_map'd sharded
    pipeline differ only in how they produce (senders, receivers, emask), so
    both wrap this one function — equivalence between them is then purely a
    property of the graphs they build.

    Composed as featurize -> one physics step from a zero state: with the
    default config (``rollout_integrator='direct'``,
    ``rollout_state_feats=False``) the zero state is dead code and this is
    op-for-op the plain forward pass, so single-shot serving IS the T=1
    rollout (``tests/test_rollout.py`` pins bit-equality).

    Aggregation follows ``cfg.agg_impl``: all three impls (plain ``xla``
    scatter-add, receiver-``sorted`` segment reduce, ``pallas`` one-hot-MXU
    kernel) run device-side inside the jitted pipeline —
    ``segment_agg.prepare_device`` made the sort/packing jittable, so none
    of them needs host preprocessing.
    """
    featurize = make_featurizer(cfg, norm_in=norm_in)
    step = make_step_fn(cfg, norm_out=norm_out)

    def forward(params, points, normals, senders, receivers, emask):
        graph = featurize(points, normals, senders, receivers, emask)
        state0 = jnp.zeros(graph["node_feats"].shape[:-1] + (cfg.node_out,),
                           jnp.float32)
        return step(params, graph, state0)

    return forward


@functools.lru_cache(maxsize=64)
def _cached_edges_fn(ms: MultiscaleSpec, knn_impl: str):
    def edges(points, n_valid):
        return multiscale_edges(points.astype(jnp.float32), n_valid, ms,
                                impl=knn_impl)
    return jax.jit(edges)


def make_edges_fn(ms: MultiscaleSpec, *, knn_impl: str = "xla",
                  jit: bool = True):
    """Graph construction alone: ``edges(points, n_valid) -> (senders,
    receivers, emask)`` with the fixed-shape layout of ``multiscale_edges``.

    The construction half of :func:`make_infer_fn`, for callers that need
    the edge list itself rather than a prediction — e.g. the mesh-free
    training data path, which builds edges on device and partitions them on
    host. The jitted variant is memoized per (spec, impl), so
    repeated calls with the same grids — clouds calibrated to identical
    resolutions — reuse one compiled program instead of re-tracing.
    """
    if jit:
        return _cached_edges_fn(ms, knn_impl)

    def edges(points, n_valid):
        return multiscale_edges(points.astype(jnp.float32), n_valid, ms,
                                impl=knn_impl)
    return edges


def device_multiscale_edges(points: np.ndarray, level_sizes, k: int, *,
                            knn_impl: str = "xla"):
    """One-shot device edge build for a host-resident nested cloud.

    Calibrates per-level grids on THIS cloud (so the hash-grid kNN matches
    the exact cKDTree answer — the calibration invariant
    ``tests/test_graphx.py`` pins), runs the jitted fixed-shape union once,
    and compacts to numpy ``(senders, receivers, level_of_edge)``. The edge
    SET equals ``repro.core.multiscale.multiscale_edges`` (slot order
    differs). This is the training-side twin of the serving pipeline: same
    construction code, host-friendly output for partitioning.
    """
    from repro.graphx import hashgrid
    pts = np.asarray(points, np.float32)
    levels = tuple(level_sizes)
    if pts.shape[0] != levels[-1]:
        raise ValueError(f"points ({pts.shape[0]}) must match finest level "
                         f"({levels[-1]})")
    grids = tuple(hashgrid.calibrate_spec(pts[:n], k, n_points=n)
                  for n in levels)
    ms = MultiscaleSpec(level_sizes=levels, k=k, grids=grids)
    s, r, em = make_edges_fn(ms, knn_impl=knn_impl)(
        jnp.asarray(pts), levels[-1])
    em = np.asarray(em)
    return (np.asarray(s)[em].astype(np.int32),
            np.asarray(r)[em].astype(np.int32),
            ms.level_of_edge[em])


def make_infer_fn(cfg: GNNConfig, ms: MultiscaleSpec, *,
                  knn_impl: str = "xla",
                  norm_in: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                  norm_out: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                  jit: bool = True):
    """Build ``infer(params, points, normals, n_valid) -> (N, node_out)``.

    points/normals: (ms.n_points, 3) padded buffers; n_valid: scalar count of
    real points (a prefix). ``norm_in``/``norm_out`` are optional (mean, std)
    pairs folded into the compiled program (input encoding / output decoding).
    """
    forward = make_graph_forward(cfg, norm_in=norm_in, norm_out=norm_out)

    def infer(params, points, normals, n_valid):
        points = points.astype(jnp.float32)
        with jax.named_scope("graphx/knn_edges"):
            senders, receivers, emask = multiscale_edges(
                points, n_valid, ms, impl=knn_impl)
        return forward(params, points, normals, senders, receivers, emask)

    return jax.jit(infer) if jit else infer


def make_batched_infer_fn(cfg: GNNConfig, ms: MultiscaleSpec, *,
                          donate: bool = False, **kw):
    """vmapped variant: (params, (B, N, 3), (B, N, 3), (B,)) -> (B, N, out).

    All requests in a batch share the bucket's static shapes; per-request
    sizes ride in ``n_valid``. ``donate=True`` donates the per-batch input
    buffers (points/normals/n_valid) to XLA so the compiled program reuses
    their memory — they are rebuilt per request anyway. Donation is a no-op
    on the CPU backend (XLA:CPU ignores it with a warning), so it is only
    requested on accelerators.
    """
    kw.pop("jit", None)
    base = make_infer_fn(cfg, ms, jit=False, **kw)
    batched = jax.vmap(base, in_axes=(None, 0, 0, 0))
    if donate and jax.default_backend() != "cpu":
        return jax.jit(batched, donate_argnums=(1, 2, 3))
    return jax.jit(batched)


def make_volume_infer_fn(cfg, spec, *, donate: bool = False):
    """The X-UNet3D bucket program: ``(params, (B, N, 3) points, (B, N, 3)
    normals, (B,) n_valid) -> (B, out_channels, X, Y, Z)`` volume fields,
    channel-major (a channel-minor layout of 4 would be padded to the
    TPU's 128 lanes).

    Per request: the signed distance and its gradient over the grid
    (``graphx.volume.sdf_grid``), then a ``lax.scan`` over
    ``cfg.n_partitions`` equal halo windows (``core.unet_halo``), each
    featurized from the map (``graphx.volume.slab_features``) and run
    through the U-Net; the owned planes of each window are written into
    the output. Requests of a batch run one after another (``lax.map``):
    a slab's activations, not the batch, set the memory. ``donate``
    follows :func:`make_batched_infer_fn`.
    """
    from repro.core import unet_halo
    from repro.graphx import volume
    from repro.models import xunet3d
    sw = unet_halo.slab_windows(cfg.grid[0], cfg.n_partitions, cfg.halo,
                                2 ** (cfg.depth - 1))

    def one(params, points, normals, n_valid):
        with jax.named_scope("graphx/volume_features"):
            sdf = volume.sdf_grid(points, normals, n_valid, spec)

        def slab(x0):
            with jax.named_scope("graphx/volume_features"):
                x = volume.slab_features(sdf, x0, sw.window, spec)
            return jnp.moveaxis(xunet3d.apply(params, cfg, x)[0], -1, 0)

        return unet_halo.scan_slabs(slab, sw,
                                    (cfg.out_channels,) + tuple(cfg.grid),
                                    jnp.float32)

    def batched(params, points, normals, n_valid):
        return jax.lax.map(lambda a: one(params, *a),
                           (points, normals, n_valid))

    if donate and jax.default_backend() != "cpu":
        return jax.jit(batched, donate_argnums=(1, 2, 3))
    return jax.jit(batched)


def make_prefill_fn(cfg: GNNConfig, ms: MultiscaleSpec, *,
                    knn_impl: str = "xla",
                    norm_in: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                    jit: bool = True):
    """Rollout prefill: ``prefill(points, normals, n_valid)`` -> graph dict.

    Builds the multi-scale edge set AND the step-invariant features in one
    jitted program — the graph-once half of graph-once/step-many. The
    returned dict has the :func:`make_featurizer` layout and is what the
    rollout engine parks in its device-resident slot table.
    """
    featurize = make_featurizer(cfg, norm_in=norm_in)

    def prefill(points, normals, n_valid):
        points = points.astype(jnp.float32)
        with jax.named_scope("graphx/knn_edges"):
            senders, receivers, emask = multiscale_edges(
                points, n_valid, ms, impl=knn_impl)
        return featurize(points, normals, senders, receivers, emask)

    return jax.jit(prefill) if jit else prefill


def make_generate_fn(cfg: GNNConfig, *, steps: int,
                     norm_out: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                     jit: bool = True, donate: bool = False):
    """Rollout generate: scan ``steps`` physics steps over a slot table.

    Returns ``gen(params, graph, state, remaining) -> (state', remaining')``
    where every graph leaf carries a leading slot axis S (vmap lanes),
    ``state`` is (S, N, node_out) and ``remaining`` (S,) int32 counts steps
    still owed per slot. Lanes are advanced only while ``remaining > 0``
    (finished/idle slots carry their state through unchanged), so one
    compiled program interleaves rollouts of different lengths and
    mid-flight arrivals. Lane independence is structural — a diverging
    (NaN) rollout cannot leak into its neighbors.

    ``donate=True`` donates state/remaining so the scan updates the slot
    table in place on accelerators (no-op on CPU, same policy as
    :func:`make_batched_infer_fn`).
    """
    step = make_step_fn(cfg, norm_out=norm_out)

    def one(params, graph, state, remaining):
        def body(carry, _):
            st, rem = carry
            with jax.named_scope("rollout/step"):
                nxt = step(params, graph, st)
            st = jnp.where(rem > 0, nxt, st)
            rem = jnp.maximum(rem - 1, 0)
            return (st, rem), None
        (state, remaining), _ = jax.lax.scan(
            body, (state, remaining), None, length=steps)
        return state, remaining

    gen = jax.vmap(one, in_axes=(None, 0, 0, 0))
    if not jit:
        return gen
    if donate and jax.default_backend() != "cpu":
        return jax.jit(gen, donate_argnums=(2, 3))
    return jax.jit(gen)
