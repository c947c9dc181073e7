"""Real-time GNN inference server: geometry in -> surface fields out.

The serving counterpart of the paper's mesh-free construction claim: requests
carry raw tessellated geometry (vertices + faces, STL-like); the server
samples a point cloud at the bucket resolution (cheap numpy, no meshing, no
cKDTree) and everything else — hash-grid kNN at every scale, multi-scale
edge union, featurization, the MeshGraphNet forward pass — runs inside one
jitted, vmapped XLA program per padding bucket.

Padding buckets: request sizes are quantized to a small set of point counts
(e.g. 1k/4k/16k). Each bucket owns static graph shapes (levels, edge buffer,
grid spec) calibrated once from a reference geometry, so the jit cache is
warm after one compile per bucket and request shapes never leak into XLA.

Autoscaling buckets (``bucket_sizes="auto"`` / ``--buckets auto``): instead
of a static ladder the server derives bucket sizes from the observed
request-size distribution. Every submit feeds an online histogram; every
``cfg.bucket_refit_every`` submits a quantile refit (``cfg.bucket_quantiles``,
rounded up to ``cfg.bucket_granularity``) adds tighter ladder targets, and a
request larger than every known size *grows* the ladder on the spot — an
oversize request is never downsampled under auto. Buckets are calibrated and
compiled on demand the first time traffic routes to them (the same
reference-geometry calibration path as a static ladder), and the compiled-
program cache is bounded: beyond ``cfg.max_live_buckets`` the least-recently-
used idle bucket is evicted and transparently rebuilt (recompiled) if its
size becomes hot again. ``ServerStats`` records the cache behavior
(``bucket_hits``/``bucket_misses``/``bucket_evictions``/``bucket_compiles``,
``grown_buckets``) and the padding waste (``padding_waste_frac``). Auto mode
works sharded and unsharded alike: a sharded bucket's per-shard shapes
(its ``ShardSpec``) are derived from the bucket size on demand
(``graphx.sharded.shard_spec_for``), so ladder growth, quantile refits and
LRU evict→rebuild apply unchanged under ``shard_devices > 1``.

Oversize requests on a *static* ladder are never silently truncated either:
the request is served at the largest bucket with a warning and an
``oversize_requests`` stat, or rejected with ``Result.error`` under
``reject_overflow=True``.

Microbatching: submitted requests queue per bucket; ``flush`` drains up to
``max_batch`` same-bucket requests per step through the bucket's batched
infer fn and records per-request latency. Drain order is deterministic:
buckets are visited in ascending size, each queue FIFO — result order is
reproducible regardless of dict insertion history or flush mode.

Async double-buffered flush (the default): ``flush`` dispatches batch ``i``
to XLA (async dispatch — the call returns as soon as the work is enqueued),
then samples/featurizes batch ``i + 1`` on the host *while the device is
busy*, and only then blocks on batch ``i``'s output. At steady state the
host-side surface sampling is hidden behind device compute instead of
serialized with it. ``async_flush=False`` restores the fully synchronous
loop (each batch sampled, dispatched and drained before the next).

Background serving: ``start(deadline_s=...)`` spawns a worker thread that
flushes a bucket as soon as it has ``max_batch`` requests queued *or* its
oldest request has waited ``deadline_s`` — latency-bounded microbatching.
``submit`` is thread-safe and wakes the worker; ``result(rid)`` blocks until
that request's prediction lands.

Aggregation: the processor scatter-add follows ``cfg.agg_impl`` (``'xla'``,
``'sorted'``, ``'pallas'`` — see ``repro.models.meshgraphnet``); all three
run device-side inside the bucket's compiled program. ``agg_impl=`` on the
server overrides the config per deployment.

Sharded serving (``shard_devices > 1``): each request is split across
devices — RCB partitions + halo rings via ``repro.graphx.sharded``, each
device building its own shard's graph under ``shard_map`` (the paper-scale
2M-point mode; see README "Sharded serving"). A bucket's ``ShardSpec``
(per-shard level capacities, merged shard-local grids, the calibrated halo
width) is derived from the bucket size when the bucket is first built and
cached per size like grid calibration, so sharded buckets ride the same
compiled-program LRU cache as unsharded ones. Up to ``max_batch`` small
geometries are *packed* into one padded sharded program call — each
geometry in its own vmap lane (the segment id), so edges, aggregations and
normalizer stats never cross geometries and each packed output equals the
request served solo. Requests whose shards outgrow the bucket's frozen
shard shapes are rejected with ``Result.error`` set, like overflow
rejections. The async flush pipelines host shard *planning* of batch i+1
against the in-flight shard_map call of batch i.

Sampling is deterministic per (server seed, request id): resubmitting a
request id reproduces its point cloud bit-for-bit regardless of what other
traffic (or warmup) ran before it.

Cold start (``repro.ckpt.compile_cache`` / ``repro.ckpt.artifact``): the
CLI always, and the library when ``cfg.compile_cache_dir`` is set, send XLA
compiles through a persistent on-disk cache, so process restarts,
autoscaler ladder growth and LRU evict→rebuild re-pay a millisecond disk
load instead of the ~0.5–2 s compile. Bucket calibration (the one host
cKDTree use) is cached per size in ``_calib`` and survives eviction, so an
evict→rebuild re-pays
at most a cache load — never recalibration. ``save_artifact``/
``from_artifact`` go further: the deploy artifact bundles params,
normalizers, the learned ladder + request-size histogram, every calibrated
grid spec and (where the backend supports it) AOT-serialized executables,
so a restored server serves its first request with ZERO XLA compiles.
``ServerStats`` splits ``bucket_compiles`` (true compiles) from
``cache_loads`` (programs obtained from the persistent cache or a
deserialized artifact executable).

Usage:
  PYTHONPATH=src python -m repro.launch.serve_gnn --requests 8 \
      --buckets 512,1024 --reduced [--shard-devices 8] [--ckpt ckpt.msgpack]
  PYTHONPATH=src python -m repro.launch.serve_gnn --requests 8 \
      --buckets auto --reduced        # traffic-derived autoscaling ladder
  PYTHONPATH=src python -m repro.launch.serve_gnn --requests 8 \
      --buckets auto --reduced --compile-cache /var/cache/xmgn \
      --save-artifact deploy.msgpack  # pre-bake the adapted ladder
  PYTHONPATH=src python -m repro.launch.serve_gnn --requests 8 \
      --artifact deploy.msgpack       # restart at warm-path latency
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import artifact as artifact_lib
from repro.ckpt import compile_cache
from repro.configs.base import GNNConfig
from repro.core.graph_build import sample_surface
from repro.data import geometry as geo
from repro.core import unet_halo
from repro.graphx import hashgrid, sharded, volume
from repro.graphx.multiscale import MultiscaleSpec
from repro.graphx.pipeline import make_batched_infer_fn, make_volume_infer_fn
from repro.launch.sharding import mesh_for_shards, shard_put
from repro.models import meshgraphnet, xunet3d
from repro.resilience import faults
from repro.telemetry import (MetricsRegistry, Telemetry,
                             default_size_buckets, warn_once)

log = logging.getLogger(__name__)

# serving-lifecycle stages recorded per batch/request (see ServerStats
# stage histograms + the per-request trace spans): submit -> queue_wait ->
# bucket_route -> drain -> prepare (sample, check_cloud) -> dispatch ->
# device_wait -> harvest -> publish -> result. ``starved`` is the time
# from a device_wait that left no execution in flight to the next program
# call: the device had nothing to run for at least that long.
SERVE_STAGES = ("queue_wait", "drain", "prepare", "sample", "check_cloud",
                "dispatch", "device_wait", "harvest", "publish", "starved",
                "compile", "cache_load")


def _level_sizes(n_points: int, n_levels: int) -> Tuple[int, ...]:
    """Nested prefix sizes n/2^(L-1) ... n (the paper's 500k/1M/2M pattern)."""
    return tuple(n_points // (2 ** (n_levels - 1 - i))
                 for i in range(n_levels))


def load_gnn_checkpoint(path: str):
    """Read a ``repro.ckpt`` GNN checkpoint (as written by ``launch.train``).

    Returns ``(params, norm_in, norm_out)`` with the normalizer stats as
    (mean, std) numpy pairs, ready for ``GNNServer(params=..., norm_in=...,
    norm_out=...)`` — the input encoding / output decoding fold into each
    bucket's compiled program.
    """
    from repro.ckpt import checkpoint as ckpt
    tree = ckpt.restore(path)
    if "params" not in tree:
        raise ValueError(f"{path} is not a GNN training checkpoint "
                         "(missing 'params')")

    def stats(d):
        if d is None:
            return None
        return (np.asarray(d["mean"], np.float32),
                np.asarray(d["std"], np.float32))

    return (tree["params"], stats(tree.get("norm_in")),
            stats(tree.get("norm_out")))


@dataclass
class Bucket:
    """One padding bucket: static shapes + its compiled batched infer fn."""
    n_points: int
    ms: MultiscaleSpec                 # graph grids; for the unet family
                                       # the band search's VolumeSpec
    infer: object                      # jitted batched fn (unsharded mode)
    compiles: int = 0                  # ACTUAL XLA compiles (backend built it)
    cache_loads: int = 0               # programs loaded, not compiled (disk
                                       # compilation cache / AOT artifact)
    aot: bool = False                  # infer is a deserialized executable
    served: int = 0
    last_used: int = 0                 # LRU tick (autoscaler eviction order)
    sspec: Optional[sharded.ShardSpec] = None   # sharded mode only
    shard_infer: object = None                  # jitted shard_map fn
    plan_sig: Optional[tuple] = None            # sspec.signature(): the
                                                # (size, plan) cache identity


@dataclass
class Request:
    verts: np.ndarray
    faces: np.ndarray
    request_id: int
    n_points: Optional[int] = None     # desired resolution (bucket-quantized)
    t_submit: float = 0.0
    deadline: Optional[float] = None   # perf_counter() time after which the
                                       # request is dropped, not served


@dataclass
class Result:
    request_id: int
    points: np.ndarray                 # (n, 3) sampled surface points
    fields: np.ndarray                 # (n, node_out) predicted fields; for
                                       # the unet family (X, Y, Z, out) over
                                       # the whole voxel grid
    latency_s: float
    bucket: int
    batch_size: int
    error: Optional[str] = None        # set on rejected requests (fields NaN)


@dataclass
class ServerStats:
    """Serving counters + bounded streaming timing stats.

    Latencies, batch sizes and per-stage timings stream into fixed-bucket
    histograms in ``metrics`` (a :class:`repro.telemetry.MetricsRegistry`)
    — O(n_buckets) memory under unbounded traffic, unlike the append-
    forever lists this replaced (a real leak under sustained load). A
    bounded recent window (``recent_cap`` newest values) is kept for
    debugging and exact small-run assertions; :attr:`latencies_s` /
    :attr:`batch_sizes` expose it with the pre-histogram names.

    Scalar counter mutations and :meth:`report` synchronize on ``lock``
    (the background worker appends while clients introspect); histograms
    carry their own per-metric locks.
    """
    metrics: MetricsRegistry = field(default_factory=MetricsRegistry)
    recent_cap: int = 1024
    t_serving: float = 0.0
    overflow_requests: int = 0         # clouds that exceeded a grid's cap
    rejected_requests: int = 0         # returned with Result.error set
    oversize_requests: int = 0         # asked for more than the static ladder
    bucket_hits: int = 0               # served by an already-live bucket
    bucket_misses: int = 0             # bucket had to be (re)built
    bucket_evictions: int = 0          # cold compiled programs dropped (LRU)
    bucket_compiles: int = 0           # actual XLA compiles across buckets
    cache_loads: int = 0               # programs obtained WITHOUT compiling:
                                       # persistent-compile-cache disk hits +
                                       # deserialized artifact executables
    bucket_calibrations: int = 0       # host cKDTree grid calibrations run
    grown_buckets: int = 0             # ladder sizes added for oversize asks
    padding_points: int = 0            # computed-but-unrequested points
    requested_points: int = 0          # points actually asked for
    # resilience counters (each mirrored to a Prometheus counter
    # serve_<name>_total via bump(), so monitors see them live)
    timed_out_requests: int = 0        # deadline expired before device work
    rejected_overload: int = 0         # shed by bounded admission control
    nonfinite_results: int = 0         # NaN/Inf caught at harvest
    worker_crashes: int = 0            # _serve_loop died (supervised)
    worker_restarts: int = 0           # supervisor restarts after a crash
    quarantined_buckets: int = 0       # sizes pulled after build/compile fail
    bucket_fallbacks: int = 0          # batches served by a larger bucket
    late_dispatches: int = 0           # program calls that found the previous
                                       # execution already finished: the
                                       # device idled for an unmeasured time
    volume_voxels_owned: int = 0       # unet family: voxels answered
    volume_voxels_computed: int = 0    # unet family: voxels run through the
                                       # U-Net, halo windows included
    lock: threading.Lock = field(default_factory=threading.Lock,
                                 repr=False, compare=False)

    _RESILIENCE = ("timed_out_requests", "rejected_overload",
                   "nonfinite_results", "worker_crashes", "worker_restarts",
                   "quarantined_buckets", "bucket_fallbacks")
    _COUNTERS = _RESILIENCE + ("late_dispatches", "volume_voxels_owned",
                               "volume_voxels_computed")

    def __post_init__(self):
        self._recent_lat: deque = deque(maxlen=self.recent_cap)
        self._recent_batch: deque = deque(maxlen=self.recent_cap)
        self._bind_metrics()

    def _bind_metrics(self):
        m = self.metrics
        self._h_latency = m.histogram(
            "serve_request_latency_seconds",
            help="submit->result latency per served request")
        self._h_batch = m.histogram(
            "serve_batch_size", buckets=default_size_buckets(1, 4096),
            help="requests per dispatched microbatch")
        self._h_stage = {
            s: m.histogram(f"serve_{s}_seconds",
                           help=f"serving stage time: {s}")
            for s in SERVE_STAGES}
        # counters monitors can alert on + health gauges
        self._counters = {
            name: m.counter(f"serve_{name}_total",
                            help=f"serving counter: {name}")
            for name in self._COUNTERS}
        self.g_worker_alive = m.gauge(
            "serve_worker_alive",
            help="1 while the background serve worker is running")
        self.g_queue_depth = m.gauge(
            "serve_queue_depth", help="requests currently queued")
        self.g_last_flush = m.gauge(
            "serve_last_flush_timestamp",
            help="unix time the worker last published results")

    def bump(self, name: str, n: int = 1):
        """Increment a counter of ``_COUNTERS`` (scalar field +
        Prometheus)."""
        with self.lock:
            setattr(self, name, getattr(self, name) + n)
        self._counters[name].inc(n)

    # ------------------------------------------------------------ recording

    @property
    def latencies_s(self) -> List[float]:
        """Recent-window request latencies (bounded; newest ``recent_cap``)."""
        with self.lock:
            return list(self._recent_lat)

    @property
    def batch_sizes(self) -> List[int]:
        """Recent-window dispatched batch sizes (bounded)."""
        with self.lock:
            return list(self._recent_batch)

    def record_latency(self, lat_s: float):
        self._h_latency.observe(lat_s)
        with self.lock:
            self._recent_lat.append(lat_s)

    def record_batch(self, size: int):
        self._h_batch.observe(size)
        with self.lock:
            self._recent_batch.append(int(size))

    def stage_hist(self, stage: str):
        """The ``serve_<stage>_seconds`` histogram (see ``SERVE_STAGES``)."""
        h = self._h_stage.get(stage)
        if h is None:
            h = self._h_stage[stage] = self.metrics.histogram(
                f"serve_{stage}_seconds",
                help=f"serving stage time: {stage}")
        return h

    def record_stage(self, stage: str, dt_s: float):
        """One observation of a lifecycle stage measured elsewhere."""
        self.stage_hist(stage).observe(dt_s)

    def reset(self):
        """Zero every counter and histogram (keeps the lock and registry
        identity); used between bench phases."""
        with self.lock:
            self.t_serving = 0.0
            self.overflow_requests = 0
            self.rejected_requests = 0
            self.oversize_requests = 0
            self.bucket_hits = 0
            self.bucket_misses = 0
            self.bucket_evictions = 0
            self.bucket_compiles = 0
            self.cache_loads = 0
            self.bucket_calibrations = 0
            self.grown_buckets = 0
            self.padding_points = 0
            self.requested_points = 0
            for name in self._COUNTERS:
                setattr(self, name, 0)
            self._recent_lat.clear()
            self._recent_batch.clear()
        self.metrics.reset()
        self._bind_metrics()

    def stage_report(self) -> dict:
        """Per-stage latency breakdown from the streaming histograms:
        ``{stage: {count, mean_ms, p50_ms, p95_ms, total_s}}``."""
        out = {}
        for s, h in sorted(self._h_stage.items()):
            n = h.count
            out[s] = {
                "count": n,
                "mean_ms": h.mean * 1e3,
                "p50_ms": (h.percentile(50) * 1e3) if n else 0.0,
                "p95_ms": (h.percentile(95) * 1e3) if n else 0.0,
                "total_s": h.sum,
            }
        return out

    def report(self) -> dict:
        with self.lock:                # snapshot: the worker may be appending
            t_serving = self.t_serving
            counters = {
                "overflow_requests": self.overflow_requests,
                "rejected_requests": self.rejected_requests,
                "oversize_requests": self.oversize_requests,
                "bucket_hits": self.bucket_hits,
                "bucket_misses": self.bucket_misses,
                "bucket_evictions": self.bucket_evictions,
                "bucket_compiles": self.bucket_compiles,
                "cache_loads": self.cache_loads,
                "bucket_calibrations": self.bucket_calibrations,
                "grown_buckets": self.grown_buckets,
            }
            counters.update({name: getattr(self, name)
                             for name in self._COUNTERS})
            padded = self.padding_points
            requested = self.requested_points
        n = self._h_latency.count
        # empty case: explicit zeros, never percentiles of fabricated data
        rep = {
            "requests": n,
            "p50_ms": self._h_latency.percentile(50) * 1e3 if n else 0.0,
            "p95_ms": self._h_latency.percentile(95) * 1e3 if n else 0.0,
            "p99_ms": self._h_latency.percentile(99) * 1e3 if n else 0.0,
            "mean_batch": self._h_batch.mean,
            "throughput_rps": n / max(t_serving, 1e-9),
            "padding_waste_frac": padded / max(padded + requested, 1),
            "stages": self.stage_report(),
        }
        rep.update(counters)
        return rep


@dataclass
class _InFlight:
    """One dispatched batch: host bookkeeping + the un-synced device output.

    Created by ``_dispatch`` (which returns before the XLA call finishes —
    async dispatch), consumed by ``_harvest`` (which blocks). ``results``
    carries rejections resolved at prepare time, in submission order.
    """
    bucket: Optional[Bucket]           # None on all-rejected error items
    results: List[Result]
    ok_reqs: List[Request]
    out: object                        # device array, or None (all rejected)
    pts: np.ndarray                    # host copy of the sampled clouds
    record: bool
    plan: object = None                # sharded mode: the ShardPlan


class GNNServer:
    """Batched multi-geometry inference with padding buckets.

    ``params`` defaults to randomly initialized weights; pass trained
    weights directly or load them with :meth:`from_checkpoint`.
    ``async_flush`` selects the double-buffered flush loop (host sampling
    overlapped with the in-flight XLA call); ``agg_impl`` overrides
    ``cfg.agg_impl`` for the processor scatter-add.

    The model family follows ``cfg.family``: ``"gnn"`` (``GNNConfig``)
    answers each sampled point with surface fields; ``"unet"``
    (``UNetConfig``, X-UNet3D) answers the whole voxel grid around the
    geometry with volume fields, from the same sampled cloud, through the
    same worker (its bucket program is
    ``graphx.pipeline.make_volume_infer_fn``). The unet family has no
    sharded, rollout or deploy-artifact mode.

    ``bucket_sizes`` is either a static ladder of point counts or the
    string ``"auto"``: the autoscaler then starts with an empty ladder and
    derives bucket sizes from traffic (see the module docstring). Passing a
    ladder together with ``cfg.bucket_policy == "auto"`` seeds the
    autoscaler with those sizes. The auto policy applies sharded and
    unsharded alike (sharded buckets derive their ShardSpec per size).
    """

    FAMILIES = ("gnn", "unet")

    def __init__(self, cfg: GNNConfig,
                 bucket_sizes: Union[str, Sequence[int]] = (1024,),
                 *, params=None, max_batch: int = 4, n_levels: int = 3,
                 knn_impl: str = "xla", agg_impl: Optional[str] = None,
                 norm_in=None, norm_out=None, seed: int = 0,
                 reference=None, check_requests: bool = True,
                 reject_overflow: bool = False, shard_devices: int = 1,
                 shard_pad_factor: Optional[float] = None,
                 async_flush: bool = True,
                 donate: bool = True, telemetry: Optional[Telemetry] = None,
                 max_queue_depth: Optional[int] = None,
                 shed_policy: Optional[str] = None,
                 request_timeout_s: Optional[float] = None,
                 worker_max_restarts: Optional[int] = None,
                 _restore: Optional[dict] = None):
        # persistent XLA compile cache: recompiles of previously-seen bucket
        # programs (restart, ladder growth, LRU evict→rebuild) hit disk
        if cfg.compile_cache_dir:
            compile_cache.enable(cfg.compile_cache_dir)
        if cfg.family not in self.FAMILIES:
            raise ValueError(f"GNNServer serves the families "
                             f"{self.FAMILIES}, not {cfg.family!r}")
        self.is_volume = cfg.family == "unet"
        if self.is_volume and int(shard_devices) > 1:
            raise ValueError("the unet family has no sharded serving mode: "
                             "its slabs run inside one program on one "
                             "device (shard_devices must be 1)")
        if agg_impl is not None and not self.is_volume:
            cfg = cfg.replace(agg_impl=agg_impl)
        if (not self.is_volume and cfg.agg_impl == "pallas"
                and int(shard_devices) == 1):
            # the batched pipeline vmaps the exactness lax.cond into a
            # select, which executes BOTH the kernel and its fallback every
            # layer; the kernel path is meant for the unbatched per-shard
            # pipeline (shard_devices > 1) or training
            warnings.warn(
                "agg_impl='pallas' under the batched (vmapped) serving "
                "path runs both the kernel and the scatter-add fallback "
                "per layer — use it with shard_devices > 1, or prefer "
                "'sorted'/'xla' here")
        if cfg.bucket_policy not in ("static", "auto"):
            raise ValueError(
                f"cfg.bucket_policy must be 'static' or 'auto', "
                f"got {cfg.bucket_policy!r}")
        self.auto = bucket_sizes == "auto" or cfg.bucket_policy == "auto"
        seed_sizes = () if bucket_sizes == "auto" else \
            tuple(sorted(int(b) for b in bucket_sizes))
        if not self.auto and not seed_sizes:
            raise ValueError("a static server needs at least one bucket "
                             "size (or pass bucket_sizes='auto')")
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.n_levels = int(n_levels)
        self.check_requests = check_requests
        self.reject_overflow = reject_overflow
        self.shard_devices = int(shard_devices)
        self.shard_pad_factor = float(cfg.shard_pad_factor
                                      if shard_pad_factor is None
                                      else shard_pad_factor)
        self.async_flush = bool(async_flush)
        init = xunet3d.init if self.is_volume else meshgraphnet.init
        self.params = params if params is not None else init(
            jax.random.PRNGKey(seed), cfg)
        # unet family: the bucket program's slab windows and output width
        self._slabs = (unet_halo.slab_windows(
            cfg.grid[0], cfg.n_partitions, cfg.halo, 2 ** (cfg.depth - 1))
            if self.is_volume else None)
        self._n_out = cfg.out_channels if self.is_volume else cfg.node_out
        self.seed = int(seed)
        self._knn_impl = knn_impl
        self._norm_in = norm_in
        self._norm_out = norm_out
        self._donate = donate
        self._queues: Dict[int, deque] = {}
        self._buckets: Dict[int, Bucket] = {}
        self._ladder: set = set(seed_sizes)   # target sizes (incl. not-live)
        # calibration cache: one MultiscaleSpec per size, kept across LRU
        # evictions and seedable from a deploy artifact — an evict→rebuild
        # re-pays at most a compile-cache load, never host recalibration
        self._calib: Dict[int, MultiscaleSpec] = {}
        # sharded sibling of _calib: one frozen ShardSpec per bucket size
        # (per-shard capacities + merged grids + halo width), derived on
        # demand from the bucket size (graphx.sharded.shard_spec_for) and
        # kept across evictions / restorable from a deploy artifact
        self._shard_calib: Dict[int, sharded.ShardSpec] = {}
        # AOT executables deserialized from a deploy artifact, consumed by
        # _build_bucket so the bucket's first dispatch runs a pre-compiled
        # program (zero traces, zero XLA compiles)
        self._aot: Dict[int, object] = {}
        self._size_hist: deque = deque(maxlen=max(int(cfg.bucket_hist_len),
                                                  1))
        self._refit_count = 0
        self._tick = 0                        # LRU clock for bucket eviction
        self._plan_sizes: set = set()         # sizes in the active drain plan
        # telemetry: span tracer gated by cfg.telemetry (no-op object when
        # off), metrics registry always live — it backs ServerStats
        self.telemetry = (telemetry if telemetry is not None
                          else Telemetry.from_config(cfg))
        self.stats = ServerStats(metrics=self.telemetry.metrics)
        self._warn_once = warn_once(log)
        self._next_id = 0
        self._cond = threading.Condition()
        self._serve_lock = threading.Lock()
        self._done: Dict[int, Result] = {}
        self._done_cap = 4096
        self._waiting: set = set()        # rids with a blocked result() call
        self._worker: Optional[threading.Thread] = None
        self._stop_flag = False
        self._deadline_s = 0.0
        # resilience knobs (constructor overrides the config's defaults)
        self.max_queue_depth = int(cfg.max_queue_depth
                                   if max_queue_depth is None
                                   else max_queue_depth)
        self.shed_policy = (cfg.shed_policy if shed_policy is None
                            else shed_policy)
        if self.shed_policy not in ("reject", "block"):
            raise ValueError("shed_policy must be 'reject' or 'block', "
                             f"got {self.shed_policy!r}")
        self.request_timeout_s = float(cfg.request_timeout_s
                                       if request_timeout_s is None
                                       else request_timeout_s)
        self.worker_max_restarts = int(cfg.worker_max_restarts
                                       if worker_max_restarts is None
                                       else worker_max_restarts)
        self._quarantined: set = set()    # sizes pulled after build/compile
                                          # failures (excluded from routing)
        self._inflight: List[Request] = []  # popped from queues, result not
                                            # yet published (crash cleanup)
        self._worker_dead = False         # supervision gave up: every submit
                                          # resolves to an immediate error
        self._restarts = 0
        self._rollout = None              # lazy RolloutEngine (rollout_engine)
        # device-starved accounting (_call_compiled / _harvest): the output
        # of the newest program call until it is harvested, and when the
        # harvest that left no execution in flight ended
        self._last_out = None
        self._idle_since: Optional[float] = None
        self._mesh = (mesh_for_shards(self.shard_devices)
                      if self.shard_devices > 1 else None)
        # grid specs are calibrated from a reference geometry representative
        # of the traffic; pass (verts, faces) to match your fleet
        ref_verts, ref_faces = reference if reference is not None else \
            geo.car_surface(geo.sample_params(0))
        self._reference = (ref_verts, ref_faces)
        if _restore:
            # deploy-artifact state (from_artifact): learned ladder +
            # request-size histogram, calibrated specs, AOT executables
            self._calib.update(_restore.get("calib", {}))
            # only specs matching THIS server's shard topology are usable;
            # a changed shard_devices/n_mp_layers recalibrates on demand
            self._shard_calib.update(
                {n: s for n, s in _restore.get("shard_calib", {}).items()
                 if s.n_shards == self.shard_devices
                 and s.halo_hops == cfg.n_mp_layers})
            self._aot.update(_restore.get("aot", {}))
            self._ladder |= set(_restore.get("ladder", ()))
            for s in _restore.get("size_hist", ()):
                self._size_hist.append(int(s))
        for n in seed_sizes:
            self._buckets[n] = self._build_bucket(n)
            self._queues[n] = deque()

    def _sample_reference(self, n: int):
        """Deterministic n-point sample of the calibration reference."""
        ref_verts, ref_faces = self._reference
        return sample_surface(ref_verts, ref_faces, n,
                              np.random.default_rng(0))

    def _calibrate(self, n: int) -> MultiscaleSpec:
        """Grid calibration for one bucket size, cached per size.

        The cache entry outlives the bucket: an LRU-evicted bucket that
        becomes hot again — or a server restored from a deploy artifact
        (which ships every spec) — reuses the spec instead of re-paying the
        host cKDTree calibration. ``stats.bucket_calibrations`` counts the
        actual calibrations run, so tests can pin "evict→rebuild never
        recalibrates".
        """
        ms = self._calib.get(n)
        if ms is not None:
            return ms
        faults.fire("bucket.calibrate")
        cfg = self.cfg
        if self.is_volume:
            ms = volume.calibrate_spec(self._sample_reference(n)[0], cfg, n)
            self._calib[n] = ms
            with self.stats.lock:
                self.stats.bucket_calibrations += 1
            return ms
        levels = _level_sizes(n, self.n_levels)
        ref_pts, _ = self._sample_reference(n)
        grids = tuple(hashgrid.calibrate_spec(ref_pts[:m], cfg.k_neighbors,
                                              n_points=m)
                      for m in levels)
        ms = MultiscaleSpec(level_sizes=levels, k=cfg.k_neighbors,
                            grids=grids)
        self._calib[n] = ms
        with self.stats.lock:
            self.stats.bucket_calibrations += 1
        return ms

    def _calibrate_shard(self, n: int, ms: MultiscaleSpec
                         ) -> sharded.ShardSpec:
        """ShardSpec derivation for one bucket size, cached per size.

        The sharded sibling of :meth:`_calibrate`: per-shard level
        capacities, merged shard-local grids and the geometric halo width
        are all functions of ``(bucket size, shard_devices, n_mp_layers,
        shard_pad_factor)`` plus the calibration reference — deterministic,
        so an evict→rebuild (or an artifact restore, which ships the specs)
        reproduces the identical compiled-program signature without
        re-planning the reference.
        """
        sspec = self._shard_calib.get(n)
        if sspec is not None:
            return sspec
        faults.fire("bucket.calibrate")
        cfg = self.cfg
        ref_pts, ref_nrm = self._sample_reference(n)
        sspec = sharded.shard_spec_for(
            n, self.shard_devices, cfg.n_mp_layers, self.shard_pad_factor,
            reference_points=ref_pts, reference_normals=ref_nrm,
            level_sizes=ms.level_sizes, k=cfg.k_neighbors, ms=ms)
        self._shard_calib[n] = sspec
        with self.stats.lock:
            self.stats.bucket_calibrations += 1
        return sspec

    def _build_bucket(self, n: int) -> Bucket:
        """Calibrate + wire one padding bucket.

        Calibration goes through the per-size ``_calibrate`` cache (the
        only cKDTree use in the server, never in the request path, never
        re-paid on evict→rebuild). The XLA compile itself happens lazily on
        the bucket's first dispatch and is counted in ``Bucket.compiles``
        / ``ServerStats.bucket_compiles`` — unless the program comes from
        a deploy artifact's AOT executable or the persistent compilation
        cache, which count as ``cache_loads`` instead.
        """
        cfg = self.cfg
        faults.fire("bucket.build")
        ms = self._calibrate(n)
        if self.shard_devices > 1:
            # per-shard shapes/grids are a function of the bucket size
            # (cached per size like _calibrate); per-request planning is
            # then cKDTree-free geometric numpy against the frozen spec
            sspec = self._calibrate_shard(n, ms)
            aot = self._aot.get(n)
            if aot is not None:
                b = Bucket(n_points=n, ms=ms, infer=None, aot=True,
                           sspec=sspec, shard_infer=aot,
                           plan_sig=sspec.signature())
                b.cache_loads += 1
                with self.stats.lock:
                    self.stats.cache_loads += 1
                return b
            shard_infer = sharded.make_sharded_infer_fn(
                cfg, sspec, self._mesh, knn_impl=self._knn_impl,
                norm_in=self._norm_in, norm_out=self._norm_out,
                pack_width=self.max_batch)
            return Bucket(n_points=n, ms=ms, infer=None, sspec=sspec,
                          shard_infer=shard_infer,
                          plan_sig=sspec.signature())
        aot = self._aot.get(n)
        if aot is not None:
            # deploy-artifact executable: already compiled, no jit cache —
            # the whole program was obtained without an XLA compile
            b = Bucket(n_points=n, ms=ms, infer=aot, aot=True)
            b.cache_loads += 1
            with self.stats.lock:
                self.stats.cache_loads += 1
            return b
        return Bucket(n_points=n, ms=ms, infer=self._make_infer(ms))

    def _make_infer(self, ms):
        """The jitted batched program of an unsharded bucket."""
        if self.is_volume:
            return make_volume_infer_fn(self.cfg, ms, donate=self._donate)
        return make_batched_infer_fn(self.cfg, ms, knn_impl=self._knn_impl,
                                     norm_in=self._norm_in,
                                     norm_out=self._norm_out,
                                     donate=self._donate)

    @classmethod
    def from_checkpoint(cls, path: str, cfg: GNNConfig,
                        bucket_sizes: Union[str, Sequence[int]] = (1024,),
                        **kw):
        """Serve trained weights: load params + normalizer stats from a
        ``launch.train`` checkpoint (the ROADMAP checkpoint-loading item).
        ``bucket_sizes`` accepts ``"auto"`` like the constructor."""
        params, norm_in, norm_out = load_gnn_checkpoint(path)
        return cls(cfg, bucket_sizes, params=params,
                   norm_in=norm_in, norm_out=norm_out, **kw)

    # ------------------------------------------------------ deploy artifacts

    # server-construction knobs carried inside the artifact so from_artifact
    # rebuilds an identical server; the AOT-relevant subset is the set of
    # knobs baked into the compiled programs (overriding one of those at
    # restore time drops the executables and falls back to jit + the
    # persistent compilation cache)
    _ARTIFACT_KNOBS = ("max_batch", "n_levels", "seed", "check_requests",
                      "reject_overflow", "async_flush", "shard_devices",
                      "shard_pad_factor")
    _AOT_KNOBS = ("max_batch", "n_levels", "shard_devices",
                  "shard_pad_factor")

    def _bucket_arg_specs(self, n: int):
        """ShapeDtypeStructs of one unsharded bucket's call signature."""
        p_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
            self.params)
        rows = self.max_batch
        f32, i32 = np.float32, np.int32
        return (p_sds, jax.ShapeDtypeStruct((rows, n, 3), f32),
                jax.ShapeDtypeStruct((rows, n, 3), f32),
                jax.ShapeDtypeStruct((rows,), i32))

    def _shard_arg_specs(self, n: int):
        """ShapeDtypeStructs of one SHARDED bucket's call signature: the
        (P[, G], Nmax, ...) batch laid out on the shard mesh, exactly what
        ``shard_put(plan.batch())`` / ``shard_put(pack.batch())`` produce."""
        from jax.sharding import NamedSharding, PartitionSpec
        sspec = self._shard_calib[n]
        sh = NamedSharding(self._mesh, PartitionSpec("data"))
        p_sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
            self.params)
        mid = (self.max_batch,) if self.max_batch > 1 else ()
        shards, nmax = sspec.n_shards, sspec.n_points
        n_levels = len(sspec.ms.level_sizes)

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

        batch = {
            "points": sds((shards, *mid, nmax, 3), np.float32),
            "normals": sds((shards, *mid, nmax, 3), np.float32),
            "level_counts": sds((shards, *mid, n_levels), np.int32),
            "recv_ok": sds((shards, *mid, nmax), bool),
            "send_ok": sds((shards, *mid, nmax), bool),
            "owned": sds((shards, *mid, nmax), bool),
        }
        return (p_sds, batch)

    def compiled(self, n_points: int):
        """The ``jax.stages.Compiled`` program of the live bucket of
        ``n_points`` points, lowered from the bucket's own argument specs —
        on a warm server a compile-cache hit, not a second compile.

        Its ``as_text()`` names every HLO instruction with the named-scope
        path it came from (``graphx/knn_edges``, ``mgn/message_passing``),
        which a device trace's op events lack; ``memory_analysis()`` gives
        the program's scratch, which ``memory_stats()`` peaks leave out.
        Raises ``KeyError`` for a size with no live bucket.
        """
        b = self._buckets[n_points]
        sharded_mode = b.sspec is not None
        infer = b.shard_infer if sharded_mode else b.infer
        if b.aot or not hasattr(infer, "lower"):
            # the bucket itself runs a deserialized executable: rebuild
            # the jittable fn just for lowering
            if sharded_mode:
                infer = sharded.make_sharded_infer_fn(
                    self.cfg, b.sspec, self._mesh,
                    knn_impl=self._knn_impl, norm_in=self._norm_in,
                    norm_out=self._norm_out, pack_width=self.max_batch)
            else:
                infer = self._make_infer(b.ms)
        arg_specs = self._shard_arg_specs(n_points) if sharded_mode else \
            self._bucket_arg_specs(n_points)
        return infer.lower(*arg_specs).compile()

    def save_artifact(self, path: str) -> dict:
        """Freeze this server's learned + compiled state into one file.

        The artifact bundles params + normalizers, the autoscaler's ladder
        and request-size histogram, every calibrated grid spec, and an
        AOT-compiled executable per live bucket (where the backend supports
        serialization) — everything :meth:`from_artifact` needs to serve
        the first request with zero XLA compiles and zero recalibration.
        Returns a small summary dict (bucket sizes, aot sizes, path).
        The unet family has no artifact (``ValueError``).

        Sharded servers are supported like unsharded ones: the artifact
        additionally freezes every calibrated ShardSpec (per-shard
        capacities, merged grids, halo width) and attempts AOT lowering of
        the shard_map programs against mesh-laid-out arg specs; where the
        backend cannot serialize them the restored server falls back to
        jit + the persistent compilation cache, never recalibration.
        """
        if self.is_volume:
            raise ValueError("deploy artifacts hold GNN buckets only; the "
                             "unet family has none")
        with self._cond:
            live = sorted(self._buckets)
            ladder = sorted(set(self._buckets) | self._ladder)
            size_hist = [int(s) for s in self._size_hist]
        # calibrate every ladder target (cheap for live sizes: cached), so
        # the restored server never runs the host cKDTree — nor, sharded,
        # re-plans the reference for its ShardSpecs
        for n in ladder:
            ms = self._calibrate(n)
            if self.shard_devices > 1:
                self._calibrate_shard(n, ms)
        aot: Dict[str, bytes] = {}
        for n in live:
            try:
                compiled = self.compiled(n)
            except Exception as e:
                log.warning("AOT lowering failed for bucket %d (%s: %s); "
                            "artifact will carry specs only for this size",
                            n, type(e).__name__, e)
                continue
            blob = artifact_lib.serialize_compiled(compiled)
            if blob is not None:
                aot[str(n)] = blob

        def norm_tree(nm):
            if nm is None:
                return None
            mean, std = nm
            return {"mean": np.asarray(mean, np.float32),
                    "std": np.asarray(std, np.float32)}

        ref_verts, ref_faces = self._reference
        tree = {
            "params": self.params,
            "norm_in": norm_tree(self._norm_in),
            "norm_out": norm_tree(self._norm_out),
            "cfg": dataclasses.asdict(self.cfg),
            "knobs": {k: getattr(self, k) for k in self._ARTIFACT_KNOBS},
            "knn_impl": self._knn_impl,
            "donate": bool(self._donate),
            "auto": bool(self.auto),
            "reference": {"verts": np.asarray(ref_verts, np.float32),
                          "faces": np.asarray(ref_faces)},
            "ladder": [int(n) for n in ladder],
            "live": [int(n) for n in live],
            "size_hist": size_hist,
            "calib": {str(n): artifact_lib.pack_multiscale_spec(ms)
                      for n, ms in self._calib.items()},
            "shard_calib": {str(n): artifact_lib.pack_shard_spec(s)
                            for n, s in self._shard_calib.items()},
            "aot": aot,
        }
        artifact_lib.save_artifact(path, tree)
        return {"path": path, "buckets": live, "ladder": ladder,
                "aot_buckets": sorted(int(k) for k in aot)}

    @classmethod
    def from_artifact(cls, path: str, cfg: Optional[GNNConfig] = None, **kw):
        """Restore a server from a deploy artifact at warm-path latency.

        Rebuilds the saved server — params, normalizers, adapted ladder,
        request-size histogram, calibrated grid specs — and seeds each live
        bucket with its deserialized AOT executable, so the first request
        triggers zero traces, zero XLA compiles and zero recalibration.
        Keyword overrides are honored, but overriding a knob that is baked
        into the compiled programs (``max_batch``, ``n_levels``,
        ``knn_impl``, ``donate``, or a different ``cfg``)
        drops the executables and falls back to jit + the persistent
        compilation cache.
        """
        tree = artifact_lib.load_artifact(path)
        aot_valid = cfg is None
        if cfg is None:
            known = {f.name for f in dataclasses.fields(GNNConfig)}
            stored = {k: v for k, v in tree.get("cfg", {}).items()
                      if k in known}
            for k, v in stored.items():       # msgpack lists -> tuples
                if isinstance(v, list):
                    stored[k] = tuple(v)
            cfg = GNNConfig(**stored)
        knobs = dict(tree.get("knobs", {}))
        for k in ("knn_impl", "donate"):
            knobs[k] = tree.get(k)
        if tree.get("auto"):
            cfg = cfg.replace(bucket_policy="auto")
        for k, v in kw.items():
            if k in cls._AOT_KNOBS + ("knn_impl", "donate") \
                    and v != knobs.get(k):
                aot_valid = False
            knobs[k] = v
        knobs["donate"] = bool(knobs.get("donate", True))

        def norm_pair(d):
            if d is None:
                return None
            return (np.asarray(d["mean"], np.float32),
                    np.asarray(d["std"], np.float32))

        ref = tree["reference"]
        calib = {int(n): artifact_lib.unpack_multiscale_spec(d)
                 for n, d in tree.get("calib", {}).items()}
        shard_calib = {int(n): artifact_lib.unpack_shard_spec(d)
                       for n, d in tree.get("shard_calib", {}).items()}
        aot = {}
        if aot_valid:
            for n, blob in tree.get("aot", {}).items():
                ex = artifact_lib.deserialize_compiled(blob)
                if ex is not None:
                    aot[int(n)] = ex
        live = [int(n) for n in tree.get("live", ())]
        bucket_sizes: Union[str, Sequence[int]] = \
            tuple(live) if live else "auto"
        restore = {
            "calib": calib,
            "shard_calib": shard_calib,
            "aot": aot,
            "ladder": [int(n) for n in tree.get("ladder", ())],
            "size_hist": [int(s) for s in tree.get("size_hist", ())],
        }
        return cls(cfg, bucket_sizes, params=tree["params"],
                   norm_in=norm_pair(tree.get("norm_in")),
                   norm_out=norm_pair(tree.get("norm_out")),
                   reference=(np.asarray(ref["verts"], np.float32),
                              np.asarray(ref["faces"])),
                   _restore=restore, **knobs)

    # ------------------------------------------------- bucket ladder / cache

    def _round_up(self, n: int) -> int:
        g = max(int(self.cfg.bucket_granularity), 1)
        return ((max(int(n), 1) + g - 1) // g) * g

    def ladder(self) -> Tuple[int, ...]:
        """Live bucket sizes (calibrated, program compiled or pending)."""
        with self._cond:
            return tuple(sorted(self._buckets))

    def target_ladder(self) -> Tuple[int, ...]:
        """Every size requests can route to: live buckets + refit targets."""
        with self._cond:
            return tuple(sorted(set(self._buckets) | self._ladder))

    def bucket_for(self, n_points: Optional[int]) -> int:
        """Pure routing query: which ladder size would serve ``n_points``?

        No side effects — the submit path routes through :meth:`_route`,
        which additionally grows the auto ladder for oversize asks or (on a
        static ladder) warns and counts ``stats.oversize_requests``.
        """
        return self._route(n_points, mutate=False)

    def _route(self, n_points: Optional[int], mutate: bool) -> int:
        """Route a requested resolution to a ladder size.

        Static ladder: smallest bucket that fits; an oversize ask warns,
        counts ``stats.oversize_requests`` and returns the largest bucket
        (the request is later rejected instead under ``reject_overflow``).
        Auto: an oversize ask GROWS the ladder — a new bucket of
        ``_round_up(n_points)`` is calibrated+compiled when first drained.
        ``mutate=False`` (the public :meth:`bucket_for`) answers the same
        question without growing, warning or counting.
        """
        with self._cond:
            sizes = sorted((set(self._buckets) | self._ladder)
                           - self._quarantined)
            if not sizes and not self.auto:
                raise RuntimeError(
                    "no live bucket can serve: every ladder size is "
                    f"quarantined ({sorted(self._quarantined)}) after "
                    "build/compile failures")
            if n_points is None:
                if sizes:
                    return sizes[-1]
                n_points = 1024               # auto + empty ladder: bootstrap
            for s in sizes:
                if n_points <= s:
                    return s
            if self.auto:
                # check-and-grow atomically so concurrent submits of the
                # same oversize ask add (and count) the new size once
                s = self._round_up(n_points)
                if mutate and s not in self._ladder:
                    self._ladder.add(s)
                    with self.stats.lock:
                        self.stats.grown_buckets += 1
                return s
        if not mutate:
            return sizes[-1]
        with self.stats.lock:
            self.stats.oversize_requests += 1
        # warn-once per (condition, ladder max): sustained oversize traffic
        # logs one WARNING (+ a stdlib warning for test/CLI visibility), not
        # one line per request — repeats are DEBUG-logged and counted
        if self.reject_overflow:
            msg = (f"request for {n_points} points exceeds the largest "
                   f"bucket ({sizes[-1]}) and will be REJECTED "
                   "(reject_overflow is set); use bucket_sizes='auto' to "
                   "grow the ladder instead")
            if self._warn_once(("oversize_reject", sizes[-1]), msg):
                warnings.warn(msg)
        else:
            msg = (f"request for {n_points} points exceeds the largest "
                   f"bucket ({sizes[-1]}): serving a DOWNSAMPLED "
                   f"{sizes[-1]}-point cloud. Pass reject_overflow=True to "
                   "reject oversize requests, or bucket_sizes='auto' to "
                   "let the ladder grow instead")
            if self._warn_once(("oversize_downsample", sizes[-1]), msg):
                warnings.warn(msg)
        return sizes[-1]

    def _refit_ladder_locked(self):
        """Quantile refit (holding ``_cond``): retarget the ladder to the
        observed size distribution, keeping the current max for coverage."""
        if not self._size_hist:
            return
        hist = np.asarray(self._size_hist)
        targets = {self._round_up(int(np.quantile(hist, q)))
                   for q in self.cfg.bucket_quantiles}
        if self._ladder:
            targets.add(max(self._ladder))    # never shrink oversize coverage
        targets -= self._quarantined          # never re-target a failed size
        cap = max(int(self.cfg.max_live_buckets), 1)
        self._ladder = set(sorted(targets)[-cap:])

    def _ensure_bucket(self, n: int) -> Bucket:
        """Compiled-program cache lookup: hit bumps LRU recency, miss builds
        the bucket (reference calibration + lazy compile) and, in auto mode,
        evicts the least-recently-used idle bucket beyond the cache bound.

        "Idle" means no queued requests AND not part of the drain plan being
        executed right now — a bucket whose batch was already popped into
        the active plan has an empty queue but is about to serve, and
        evicting it would force a pointless rebuild+recompile one item
        later. The cap is therefore soft within a single plan.

        Sharded servers key the cache by ``(size, shard-plan signature)``:
        a live bucket whose compiled program was built for a ShardSpec that
        no longer matches the size's calibrated spec (e.g. the spec cache
        was re-seeded from a deploy artifact) is a MISS — it is dropped and
        rebuilt against the current spec rather than served stale.
        """
        with self._cond:
            b = self._buckets.get(n)
            if b is not None and self.shard_devices > 1:
                sc = self._shard_calib.get(n)
                if sc is not None and b.plan_sig != sc.signature():
                    del self._buckets[n]      # stale shard plan: rebuild
                    b = None
            if b is not None:
                self._tick += 1
                b.last_used = self._tick
                with self.stats.lock:
                    self.stats.bucket_hits += 1
                return b
        with self.stats.lock:
            self.stats.bucket_misses += 1
        b = self._build_bucket(n)             # slow host work: outside _cond
        with self._cond:
            self._tick += 1
            b.last_used = self._tick
            self._buckets[n] = b
            self._queues.setdefault(n, deque())
            if self.auto:
                cap = max(int(self.cfg.max_live_buckets), 1)
                while len(self._buckets) > cap:
                    idle = [s for s in self._buckets
                            if s != n and not self._queues.get(s)
                            and s not in self._plan_sizes]
                    if not idle:
                        break                 # everything else has traffic
                    victim = min(idle,
                                 key=lambda s: self._buckets[s].last_used)
                    del self._buckets[victim]
                    self._queues.pop(victim, None)
                    with self.stats.lock:
                        self.stats.bucket_evictions += 1
        return b

    # ------------------------------------------- quarantine / degradation

    def _quarantine(self, n: int, err: Exception):
        """Pull a failed size out of service: drop its bucket + ladder
        entry so no future request routes to it; traffic falls back to the
        next-larger live size (see ``_dispatch_item``). Warn-once."""
        with self._cond:
            if n in self._quarantined:
                return
            self._quarantined.add(n)
            self._buckets.pop(n, None)
            self._ladder.discard(n)
        self.stats.bump("quarantined_buckets")
        msg = (f"bucket {n} quarantined after a build/compile failure "
               f"({type(err).__name__}: {err}); traffic falls back to the "
               "next-larger live bucket")
        if self._warn_once(("quarantine", n), msg):
            warnings.warn(msg)

    def _next_size_above(self, size: int) -> Optional[int]:
        """Smallest non-quarantined routable size strictly above ``size``."""
        with self._cond:
            cands = sorted(s for s in set(self._buckets) | self._ladder
                           if s > size and s not in self._quarantined)
        return cands[0] if cands else None

    def _dispatch_item(self, n: int, batch: List[Request],
                       record: bool = True) -> _InFlight:
        """prepare+dispatch one work item, degrading past failed buckets.

        A bucket whose build or compile raises is quarantined and the
        batch retries on the next-larger live size (counted in
        ``stats.bucket_fallbacks``); only when no larger size exists does
        the failure propagate. Host-side prepare errors (bad geometry)
        propagate immediately — they are the request's fault, not the
        bucket's.
        """
        size: Optional[int] = n
        last_err: Optional[Exception] = None
        while size is not None:
            try:
                b = self._ensure_bucket(size)
            except Exception as e:
                last_err = e
                self._quarantine(size, e)
                size = self._next_size_above(size)
                continue
            if size != n:
                with self._cond:       # shield the fallback bucket from LRU
                    self._plan_sizes.add(size)
            pre, ok, samples = self._prepare(b, batch, record)
            try:
                fl = self._dispatch(b, pre, ok, samples, record)
            except Exception as e:
                last_err = e
                self._quarantine(size, e)
                size = self._next_size_above(size)
                continue
            if size != n and record:
                self.stats.bump("bucket_fallbacks")
            return fl
        raise last_err if last_err is not None else RuntimeError(
            f"no live bucket can serve size {n}")

    def _timeout_result(self, n: int, req: Request) -> Result:
        """Resolve one deadline-expired request (never reached the device)."""
        self.stats.bump("timed_out_requests")
        t = time.perf_counter()
        waited = t - (req.t_submit or t)
        return Result(request_id=req.request_id,
                      points=np.zeros((0, 3), np.float32),
                      fields=np.zeros((0, self._n_out), np.float32),
                      latency_s=waited, bucket=n, batch_size=0,
                      error=f"deadline exceeded: request waited "
                            f"{waited * 1e3:.1f} ms, dropped before "
                            "device work")

    def _resolve_error_locked(self, bucket: int, reason: str) -> int:
        """Allocate a rid and resolve it immediately as an error Result
        (shed/dead-server submits). Caller holds ``_cond``."""
        rid = self._next_id
        self._next_id += 1
        self._done[rid] = Result(
            request_id=rid, points=np.zeros((0, 3), np.float32),
            fields=np.zeros((0, self._n_out), np.float32),
            latency_s=0.0, bucket=bucket, batch_size=0, error=reason)
        self._cond.notify_all()
        return rid

    def submit(self, verts: np.ndarray, faces: np.ndarray,
               n_points: Optional[int] = None, *,
               timeout_s: Optional[float] = None) -> int:
        """Enqueue a geometry; returns the request id. Thread-safe; wakes
        the background worker (if running).

        ``timeout_s`` (default ``cfg.request_timeout_s``; 0/None = no
        deadline) bounds how long the request may wait before device work
        starts — an expired request is dropped from the plan and resolved
        as a timed-out ``Result.error`` instead of being served late.

        Bounded admission (``max_queue_depth > 0``): beyond the bound a
        ``shed_policy="reject"`` server resolves the submit immediately as
        a ``Result.error`` (counted in ``stats.rejected_overload``); under
        ``"block"`` the call waits for queue space (backpressure). A dead
        server (worker crashed beyond its restart budget, or stopped with
        requests pending) also resolves submits immediately — a client
        waiting on ``result()`` NEVER hangs because of a submit that can
        no longer be served.
        """
        # geometry copies can be multi-MB: do them OUTSIDE the lock so
        # producers never stall waiters / the worker on an array copy
        t0 = time.perf_counter()
        verts = np.asarray(verts, np.float32)
        faces = np.asarray(faces)
        if timeout_s is None:
            timeout_s = self.request_timeout_s or None
        t_route = time.perf_counter()
        bucket = self._route(n_points, mutate=True)   # auto mode may grow
        t_routed = time.perf_counter()
        with self._cond:
            if self._worker_dead:
                return self._resolve_error_locked(
                    bucket, "server worker is dead (crashed beyond its "
                    "restart budget); restart the server")
            if self.max_queue_depth > 0:
                depth = sum(len(q) for q in self._queues.values())
                if depth >= self.max_queue_depth:
                    if self.shed_policy == "reject":
                        self.stats.bump("rejected_overload")
                        return self._resolve_error_locked(
                            bucket, f"rejected: queue full "
                            f"(max_queue_depth={self.max_queue_depth}, "
                            "shed_policy='reject')")
                    # "block": backpressure the producer until the worker
                    # drains (or the server stops/dies — then resolve with
                    # an error instead of deadlocking the producer)
                    while True:
                        depth = sum(len(q) for q in self._queues.values())
                        if (depth < self.max_queue_depth
                                or self._worker is None):
                            break
                        if self._worker_dead:
                            return self._resolve_error_locked(
                                bucket, "server worker died while this "
                                "submit was blocked on queue space")
                        self._cond.wait(timeout=0.05)
            rid = self._next_id
            self._next_id += 1
            now = time.perf_counter()
            self._queues.setdefault(bucket, deque()).append(
                Request(verts=verts, faces=faces, request_id=rid,
                        n_points=n_points, t_submit=now,
                        deadline=None if not timeout_s
                        else now + float(timeout_s)))
            self.stats.g_queue_depth.set(
                sum(len(q) for q in self._queues.values()))
            if self.auto:
                self._size_hist.append(bucket if n_points is None
                                       else int(n_points))
                self._refit_count += 1
                if self._refit_count >= max(int(self.cfg.bucket_refit_every),
                                            1):
                    self._refit_count = 0
                    self._refit_ladder_locked()
            self._cond.notify_all()
        if self.telemetry.enabled:
            tracer = self.telemetry.tracer
            tracer.record_span("submit", t0, time.perf_counter(),
                               trace_id=f"req-{rid}", bucket=bucket,
                               n_points=n_points)
            tracer.record_span("bucket_route", t_route, t_routed,
                               trace_id=f"req-{rid}", bucket=bucket)
        return rid

    def pending(self) -> int:
        # snapshot under the lock: the worker pops/evicts queues concurrently
        with self._cond:
            return sum(len(q) for q in self._queues.values())

    # ------------------------------------------------------------- serving

    def warmup(self):
        """Compile each live bucket's program on a dummy batch.

        Uses the calibration reference geometry so the dummy request always
        fits the frozen shapes; a warmup rejection (possible only if the
        reference itself cannot be planned, i.e. misconfiguration) is
        surfaced instead of silently skipping the compile. ``Bucket.compiles``
        counts ACTUAL jit-cache growth — calling ``warmup`` twice compiles
        (and counts) once. Under ``bucket_sizes="auto"`` with no seed ladder
        there is nothing to warm yet; buckets compile on first traffic.
        """
        verts, faces = self._reference
        width = self.max_batch
        with self._serve_lock:
            with self._cond:
                buckets = [self._buckets[n] for n in sorted(self._buckets)]
            for b in buckets:
                batch = [Request(verts, faces, -1, b.n_points)] * width
                results = self._run_batch(b, batch, record=False)
                errs = [r.error for r in results if r.error is not None]
                if errs:
                    raise RuntimeError(
                        f"warmup failed for bucket {b.n_points}: {errs[0]}")

    def _sample(self, req: Request, n: int):
        # deterministic per (server seed, request id): independent of what
        # other traffic or warmup ran before this request
        rng = np.random.default_rng((self.seed, req.request_id + 1))
        return sample_surface(req.verts, req.faces, n, rng)

    def _check_cloud(self, b: Bucket, pts: np.ndarray, rid: int) -> int:
        """Cheap numpy guard against out-of-distribution geometries: a cloud
        denser than the calibration reference can overflow a grid's
        neighborhood capacity, which would silently drop kNN candidates —
        or, for the unet family, a cell of the band search's table, which
        would give voxels a wrong nearest sample (such a request is always
        rejected)."""
        if self.is_volume:
            dropped = volume.overflow_count(pts, b.ms)
        else:
            dropped = sum(hashgrid.overflow_count(pts[:m], m, g)
                          for m, g in zip(b.ms.level_sizes, b.ms.grids))
        if dropped:
            with self.stats.lock:
                self.stats.overflow_requests += 1
            msg = (f"request {rid}: geometry overflows bucket "
                   f"{b.n_points}'s calibrated grid ({dropped} candidate "
                   "slots dropped) — neighbor sets may be approximate; "
                   "recalibrate the server with a representative reference "
                   "geometry")
            # one WARNING per (bucket, condition), not one per request
            if self._warn_once(("grid_overflow", b.n_points), msg):
                warnings.warn(msg)
        return dropped

    def _reject(self, req: Request, n_points: int, reason: str,
                pts: np.ndarray, record: bool) -> Result:
        if record:
            with self.stats.lock:
                self.stats.rejected_requests += 1
        t = time.perf_counter()
        return Result(request_id=req.request_id, points=pts,
                      fields=self._nan_fields(n_points),
                      latency_s=t - (req.t_submit or t), bucket=n_points,
                      batch_size=0, error=reason)

    def _nan_fields(self, n_points: int) -> np.ndarray:
        """Fields of an error Result: NaN per point of the bucket; for the
        unet family, whose answer is a whole voxel grid, none."""
        rows = 0 if self.is_volume else n_points
        return np.full((rows, self._n_out), np.nan, np.float32)

    def _nonfinite_result(self, b: Bucket, req: Request,
                          vals: np.ndarray) -> Result:
        """Resolve one request whose harvested output carried NaN/Inf."""
        self.stats.bump("nonfinite_results")
        total = int(np.size(vals))
        bad = total - int(np.isfinite(vals).sum())
        msg = (f"nonfinite output detected at harvest: {bad} of {total} "
               f"values are NaN/Inf (bucket {b.n_points})")
        if self._warn_once(("nonfinite", b.n_points), msg):
            warnings.warn(msg)
        t = time.perf_counter()
        return Result(request_id=req.request_id,
                      points=np.zeros((0, 3), np.float32),
                      fields=self._nan_fields(b.n_points),
                      latency_s=t - (req.t_submit or t), bucket=b.n_points,
                      batch_size=0, error=msg)

    # ------------------------------------------- prepare / dispatch / harvest

    def _stage(self, name: str, record: bool, reqs=(), **attrs):
        """One worker stage through ``Telemetry.span``: the
        ``serve_<name>_seconds`` histogram when ``record``; with telemetry
        on, the tracer span ``name`` and the profiler annotation
        ``serve/<name>``, carrying ``attrs`` and the ids of ``reqs``."""
        if self.telemetry.enabled and reqs:
            attrs["rids"] = " ".join(str(r.request_id) for r in reqs)
        return self.telemetry.span(
            name, scope="serve",
            hist=self.stats.stage_hist(name) if record else None, **attrs)

    def _prepare(self, b: Bucket, reqs: List[Request], record: bool):
        """Host stage: sample surfaces + run OOD checks; resolve rejections.

        Pure host numpy — in the async flush this is the work that overlaps
        the previous batch's in-flight XLA call.
        """
        results: List[Result] = []
        ok_reqs, samples = [], []
        with self._stage("prepare", record, reqs, bucket=b.n_points,
                         batch=len(reqs)) as st:
            for req in reqs:
                if (self.reject_overflow and req.n_points is not None
                        and req.n_points > b.n_points):
                    # static-ladder oversize: reject instead of downsampling
                    # (under auto routing the bucket always fits the request)
                    results.append(self._reject(
                        req, b.n_points,
                        f"request for {req.n_points} points exceeds the "
                        f"largest bucket ({b.n_points}) and reject_overflow "
                        "is set; use bucket_sizes='auto' to grow the ladder",
                        np.zeros((0, 3), np.float32), record))
                    continue
                with self._stage("sample", record, bucket=b.n_points,
                                 rid=req.request_id):
                    pts, nrm = self._sample(req, b.n_points)
                dropped = 0
                if record and self.check_requests:
                    with self._stage("check_cloud", True, bucket=b.n_points,
                                     rid=req.request_id):
                        dropped = self._check_cloud(b, pts, req.request_id)
                if dropped and (self.reject_overflow or self.is_volume):
                    results.append(self._reject(
                        req, b.n_points,
                        f"grid overflow: {dropped} candidate slots "
                        "dropped (geometry denser than calibration "
                        "reference)", pts, record))
                    continue
                ok_reqs.append(req)
                samples.append((pts, nrm))
            st.set(ok=len(ok_reqs))
        return results, ok_reqs, samples

    def _dispatch(self, b: Bucket, pre: List[Result], ok_reqs: List[Request],
                  samples, record: bool) -> _InFlight:
        """Device stage: pad, transfer, enqueue the XLA call; NO blocking.

        Returns immediately with the un-synced output array (JAX async
        dispatch) so the caller can do host work for the next batch while
        this one runs.
        """
        with self._stage("dispatch", record and bool(ok_reqs), ok_reqs,
                         bucket=b.n_points, batch=len(ok_reqs)):
            return self._dispatch_inner(b, pre, ok_reqs, samples, record)

    def _dispatch_inner(self, b: Bucket, pre: List[Result],
                        ok_reqs: List[Request], samples,
                        record: bool) -> _InFlight:
        if not ok_reqs:
            return _InFlight(bucket=b, results=pre, ok_reqs=[], out=None,
                             pts=np.zeros((0,)), record=record)
        faults.fire("serve.dispatch")
        if b.sspec is not None:
            # sharded: up to max_batch geometries pack into the vmap lanes
            # of ONE padded shard_map call (each lane = one segment id)
            reqs_kept: List[Request] = []
            plans: List[sharded.ShardPlan] = []
            kept_pts: List[np.ndarray] = []
            # halo width was calibrated into the spec (cached per bucket);
            # recompute from the cloud only for legacy specs without one
            width = b.sspec.halo_width or None
            for (pts, nrm), req in zip(samples, ok_reqs):
                try:
                    faults.fire("shard.plan")
                    plan = sharded.plan_shards(
                        pts, nrm, self.shard_devices, self.cfg.n_mp_layers,
                        b.ms.level_sizes, self.cfg.k_neighbors,
                        method="geometric",
                        halo_width=(width if width is not None else
                                    sharded.global_halo_width(pts, b.ms)),
                        spec=b.sspec)
                except Exception as e:
                    # a failed plan is the REQUEST's fault (its shards
                    # overflow the frozen spec, or chaos fired) — reject
                    # it and keep packing; never quarantine the bucket
                    pre = pre + [self._reject(req, b.n_points,
                                              str(e) or repr(e), pts,
                                              record)]
                    continue
                reqs_kept.append(req)
                plans.append(plan)
                kept_pts.append(pts)
            if not plans:
                return _InFlight(bucket=b, results=pre, ok_reqs=[], out=None,
                                 pts=np.zeros((0,)), record=record)
            pack = sharded.pack_plans(plans, width=self.max_batch)
            # the compiled program has a pack axis only when max_batch > 1
            dev_batch = pack.batch() if self.max_batch > 1 else \
                plans[0].batch()
            out = self._call_compiled(b, b.shard_infer, record, self.params,
                                      shard_put(dev_batch, self._mesh))
            return _InFlight(bucket=b, results=pre, ok_reqs=reqs_kept,
                             out=out, pts=np.stack(kept_pts), record=record,
                             plan=pack)
        # static batcher: always pad to max_batch rows so each bucket
        # compiles exactly once regardless of how full the microbatch is
        n = b.n_points
        rows = max(self.max_batch, len(ok_reqs))
        pts = np.zeros((rows, n, 3), np.float32)
        nrm = np.zeros((rows, n, 3), np.float32)
        for i, (p, m) in enumerate(samples):
            pts[i], nrm[i] = p, m
        for i in range(len(ok_reqs), rows):  # pad rows replay the last request
            pts[i], nrm[i] = pts[len(ok_reqs) - 1], nrm[len(ok_reqs) - 1]
        # explicit H2D put: the transfer belongs to this batch's device
        # timeline, and donation lets XLA reuse the buffers (off-CPU)
        dev_pts = jax.device_put(pts)
        dev_nrm = jax.device_put(nrm)
        out = self._call_compiled(b, b.infer, record, self.params, dev_pts,
                                  dev_nrm, jnp.full((rows,), n, jnp.int32))
        return _InFlight(bucket=b, results=pre, ok_reqs=ok_reqs, out=out,
                         pts=pts, record=record)

    def _call_compiled(self, b: Bucket, fn, record: bool, *args):
        """Invoke a bucket's jitted program, counting ACTUAL compiles.

        jit tracing/compilation happens synchronously inside the call (the
        device execution stays async), so jit-cache growth across the call
        is the number of fresh *programs* — a warm call counts zero, which
        is what makes the cache hit/eviction stats trustworthy. With the
        persistent compilation cache enabled, a fresh program may be a
        millisecond disk load rather than a true XLA compile: the
        monitoring-event deltas (``CompileEvents``) attribute the growth to
        ``bucket_compiles`` (true compiles) vs ``cache_loads``, so a
        restarted server that re-traces everything but compiles nothing
        reports zero compiles.

        Device-starved time is closed here (``record`` only): if the last
        harvest left no execution in flight, the time since it ended is a
        ``starved`` observation; if the previous execution is still
        unharvested but already finished, the device went idle at some
        unseen moment and the call counts as a ``late_dispatches``.
        """
        faults.fire("serve.compile")      # chaos: compile/OOM failure
        cache_size = getattr(fn, "_cache_size", None)
        before = cache_size() if cache_size is not None else None
        ev = compile_cache.CompileEvents() if before is not None else None
        t0 = time.perf_counter()
        late = (record and self._idle_since is None
                and self._last_out is not None
                and all(x.is_ready()
                        for x in jax.tree_util.tree_leaves(self._last_out)))
        with self._stage(f"call_b{b.n_points}", False):
            out = fn(*args)
        if record and self._idle_since is not None:
            self.stats.record_stage("starved", t0 - self._idle_since)
        elif late:
            self.stats.bump("late_dispatches")
        self._idle_since = None
        self._last_out = out
        if before is not None:
            grew = cache_size() - before
            if grew > 0:
                t1 = time.perf_counter()
                misses, hits = ev.delta()
                if misses + hits == 0:
                    # no persistent cache (or no listener): every fresh
                    # program is a backend compile, as before
                    compiles = grew
                else:
                    compiles = min(grew, misses)
                loads = grew - compiles
                b.compiles += compiles
                b.cache_loads += loads
                with self.stats.lock:
                    self.stats.bucket_compiles += compiles
                    self.stats.cache_loads += loads
                # the call's wall time on a cache miss IS the compile (trace
                # + lower + compile; device execution stays async)
                stage = "compile" if compiles else "cache_load"
                self.stats.record_stage(stage, t1 - t0)
                self.telemetry.tracer.record_span(
                    stage, t0, t1, bucket=b.n_points, compiles=compiles,
                    cache_loads=loads)
        return out

    def _padding_of(self, b: Bucket, req: Request) -> Tuple[int, int]:
        """(requested, padded-waste) point counts for one served request."""
        asked = b.n_points if req.n_points is None else \
            min(int(req.n_points), b.n_points)
        return asked, b.n_points - asked

    def _harvest(self, fl: _InFlight) -> List[Result]:
        """Sync stage: block on the device output, build Results, record.

        The ``block_until_ready`` wall time is the ``device_wait`` stage —
        how long the host actually stalled on XLA (at steady state under
        the async flush this is the device-bound part of the pipeline);
        everything after it (host copy and gather, nonfinite guard,
        bookkeeping) is ``harvest``. A recorded harvest of the newest
        program call leaves the device with nothing in flight: the
        ``starved`` interval starts where its ``device_wait`` ends.
        """
        results = list(fl.results)
        if fl.out is None:
            return results
        b, record, ok_reqs = fl.bucket, fl.record, fl.ok_reqs
        newest = fl.out is self._last_out
        if newest:
            self._last_out = None
        with self._stage("device_wait", record, bucket=b.n_points,
                         batch=len(ok_reqs)) as st:
            out_dev = jax.block_until_ready(fl.out)
        if newest and record:
            self._idle_since = st.t1
        with self._stage("harvest", record, ok_reqs, bucket=b.n_points,
                         batch=len(ok_reqs)):
            out = np.asarray(out_dev)
            out = faults.corrupt("serve.harvest", out)  # chaos: garbage
            if b.sspec is not None:
                # the host-side gather back into one cloud per geometry is
                # part of what the client waits for. A max_batch == 1
                # program has no pack axis: normalize so the PackPlan
                # de-interleave handles both layouts.
                if out.ndim == 3:
                    out = out[:, None]
                out = fl.plan.gather(out)
                # empty pack lanes replay the last geometry (PackPlan.batch)
                replay = (fl.plan.width - len(ok_reqs)
                          if self.max_batch > 1 else 0)
            else:
                # partial microbatches replay the last request to fill
                # max_batch rows (_dispatch)
                replay = max(self.max_batch, len(ok_reqs)) - len(ok_reqs)
            t_done = time.perf_counter()
            guard = self.cfg.nonfinite_guard
            lats = []
            for i, req in enumerate(ok_reqs):
                if guard and not np.isfinite(out[i]).all():
                    # nonfinite garbage never reaches a client as data —
                    # the per-ITEM scan contains the blast radius to this
                    # request (one pack lane's garbage spares its neighbors)
                    results.append(self._nonfinite_result(b, req, out[i]))
                    continue
                lat = t_done - (req.t_submit or t_done)
                lats.append(lat)
                # a volume answer is channel-major on the device (see
                # make_volume_infer_fn); the client gets (X, Y, Z, out)
                fields = (np.moveaxis(out[i], 0, -1) if self.is_volume
                          else out[i])
                results.append(Result(
                    request_id=req.request_id, points=fl.pts[i],
                    fields=fields, latency_s=lat, bucket=b.n_points,
                    batch_size=len(ok_reqs)))
                if self.telemetry.enabled:
                    self.telemetry.tracer.record_span(
                        "request", req.t_submit or t_done, t_done,
                        trace_id=f"req-{req.request_id}", bucket=b.n_points)
            if record and ok_reqs:
                padding = [self._padding_of(b, req) for req in ok_reqs]
                for lat in lats:
                    self.stats.record_latency(lat)
                self.stats.record_batch(len(ok_reqs))
                # replayed rows or lanes are discarded compute: waste too
                with self.stats.lock:
                    self.stats.requested_points += sum(a for a, _ in padding)
                    self.stats.padding_points += \
                        sum(w for _, w in padding) + replay * b.n_points
                b.served += len(ok_reqs)
                if self.is_volume:
                    sw, (_, y, z) = self._slabs, self.cfg.grid
                    self.stats.bump("volume_voxels_owned",
                                    len(lats) * self.cfg.grid[0] * y * z)
                    self.stats.bump("volume_voxels_computed",
                                    len(lats) * sw.n * sw.window * y * z)
        return results

    def _run_batch(self, b: Bucket, reqs: List[Request],
                   record: bool = True) -> List[Result]:
        """Synchronous prepare -> dispatch -> harvest of one batch."""
        pre, ok_reqs, samples = self._prepare(b, reqs, record)
        return self._harvest(self._dispatch(b, pre, ok_reqs, samples, record))

    # ------------------------------------------------------------- flushing

    def _drain_plan(self, ready_only: bool = False
                    ) -> Tuple[List[Tuple[int, List[Request]]],
                               List[Tuple[int, Request]]]:
        """Pop queued requests into (bucket size, batch) work items.

        Deterministic order: ascending bucket size, FIFO within a bucket.
        ``ready_only`` keeps batches that are full (``max_batch``) or whose
        oldest request has exceeded the background deadline; the final
        partial batch of a bucket stays queued until its deadline expires.
        Work items carry the SIZE, not the bucket: under the autoscaler a
        bucket may not be built yet — ``_run_plan`` resolves it through the
        compiled-program cache outside this lock.

        Requests whose per-request deadline has expired are filtered out
        FIRST (before batching) and returned separately as ``(size,
        request)`` pairs — they never reach device work; the caller
        resolves them as timed-out error Results.
        """
        now = time.perf_counter()
        # sharded and unsharded alike: sharded batches pack into the vmap
        # lanes of one shard_map call (see _dispatch_inner)
        width = self.max_batch
        plan: List[Tuple[int, List[Request]]] = []
        timed_out: List[Tuple[int, Request]] = []
        for n in sorted(self._queues):
            q = self._queues[n]
            if any(r.deadline is not None and now >= r.deadline for r in q):
                fresh: deque = deque()
                while q:
                    r = q.popleft()
                    if r.deadline is not None and now >= r.deadline:
                        timed_out.append((n, r))
                    else:
                        fresh.append(r)
                q.extend(fresh)
            while q:
                due = now - q[0].t_submit >= self._deadline_s
                if ready_only and len(q) < width and not due:
                    break
                plan.append((n, [q.popleft()
                                 for _ in range(min(len(q), width))]))
        # queue wait ends when the request is popped into a work plan
        t_pop = time.perf_counter()
        tracer = self.telemetry.tracer
        for n, batch in plan:
            for req in batch:
                wait = t_pop - req.t_submit
                self.stats.record_stage("queue_wait", wait)
                tracer.record_span("queue_wait", req.t_submit, t_pop,
                                   trace_id=f"req-{req.request_id}",
                                   bucket=n)
        return plan, timed_out

    def _item_error(self, n_points: int, batch: List[Request],
                    e: Exception) -> _InFlight:
        """Turn one failed work item into error Results (background mode)."""
        res = [self._reject(req, n_points, f"serving error: {e!r}",
                            np.zeros((0, 3), np.float32), True)
               for req in batch]
        return _InFlight(bucket=None, results=res, ok_reqs=[], out=None,
                         pts=np.zeros((0,)), record=True)

    def _run_plan(self, plan, async_mode: bool,
                  errors_as_results: bool = False) -> List[Result]:
        """Execute drained work items; async mode double-buffers.

        Async loop order per item j: prepare(j) [host] -> dispatch(j)
        [enqueue] -> harvest(j-1) [block]. While batch j-1 is in flight on
        the device, the host samples batch j — the overlap that hides
        sampling latency at steady state. At most two batches are in the
        XLA queue at once.

        ``errors_as_results`` (background worker): a failure is contained
        to ITS work item — that batch's requests come back as error
        Results, every other batch completes normally. Foreground flushes
        keep raising so callers see the exception.
        """
        with self._serve_lock:
            with self._cond:                  # shield plan buckets from LRU
                self._plan_sizes = {n for n, _ in plan}
            try:
                return self._run_plan_inner(plan, async_mode,
                                            errors_as_results)
            finally:
                with self._cond:
                    self._plan_sizes = set()

    def _run_plan_inner(self, plan, async_mode: bool,
                        errors_as_results: bool) -> List[Result]:
        with self.telemetry.span("flush", items=len(plan),
                                 mode="async" if async_mode else "sync"):
            return self._run_plan_body(plan, async_mode, errors_as_results)

    def _run_plan_body(self, plan, async_mode: bool,
                       errors_as_results: bool) -> List[Result]:
        results: List[Result] = []
        t0 = time.perf_counter()
        if not async_mode:
            for n, batch in plan:
                try:
                    fl = self._dispatch_item(n, batch)
                    results.extend(self._harvest(fl))
                except Exception as e:
                    if not errors_as_results:
                        raise
                    results.extend(self._item_error(n, batch, e).results)
        else:
            inflight: Optional[_InFlight] = None
            for n, batch in plan:
                try:
                    nxt = self._dispatch_item(n, batch)
                except Exception as e:
                    if not errors_as_results:
                        raise
                    nxt = self._item_error(n, batch, e)
                if inflight is not None:
                    results.extend(self._harvest_guarded(
                        inflight, errors_as_results))
                inflight = nxt
            if inflight is not None:
                results.extend(self._harvest_guarded(
                    inflight, errors_as_results))
        with self.stats.lock:
            self.stats.t_serving += time.perf_counter() - t0
        return results

    def _harvest_guarded(self, fl: _InFlight,
                         errors_as_results: bool) -> List[Result]:
        try:
            return self._harvest(fl)
        except Exception as e:
            if not errors_as_results:
                raise
            n = fl.bucket.n_points if fl.bucket is not None else 0
            return list(fl.results) + \
                self._item_error(n, fl.ok_reqs, e).results

    def flush(self, *, async_mode: Optional[bool] = None) -> List[Result]:
        """Drain every queue, up to ``max_batch`` requests per XLA call.

        ``async_mode`` overrides the server's ``async_flush`` default.
        Results come back in deterministic drain order either way.
        Incompatible with a running background worker — a foreground flush
        would steal queued requests whose results ``result()`` waiters are
        blocked on, so it raises instead.

        Deadline-expired requests come back first as timed-out error
        Results (they never reach device work), then served results in
        deterministic drain order.
        """
        self._assert_no_worker()
        with self._cond:
            plan, timed_out = self._drain_plan()
        expired = [self._timeout_result(n, req) for n, req in timed_out]
        return expired + self._run_plan(plan, self.async_flush
                                        if async_mode is None else async_mode)

    def _assert_no_worker(self):
        if self._worker is not None:
            raise RuntimeError(
                "flush()/serve() while the background worker is running "
                "would steal its queued requests; use submit()/result(), "
                "or stop() the worker first")

    def serve(self, requests: Sequence[Tuple[np.ndarray, np.ndarray,
                                             Optional[int]]]) -> List[Result]:
        """Submit + flush a stream of (verts, faces, n_points) requests.

        Guarded against a running background worker BEFORE submitting —
        otherwise the rejected call would still have leaked its requests
        into the worker's queues. Submits resolved without queueing
        (admission-shed, dead server) are merged in from the result
        buffer after the flush.
        """
        self._assert_no_worker()
        rids = [self.submit(verts, faces, n_points)
                for verts, faces, n_points in requests]
        results = self.flush()
        with self._cond:
            shed = [self._done.pop(rid) for rid in rids
                    if rid in self._done]
        return results + shed

    # ------------------------------------------------- background front-end

    def start(self, deadline_s: float = 0.02, result_cap: int = 4096):
        """Spawn the background flush worker (deadline-based microbatching).

        A bucket is flushed as soon as it holds ``max_batch`` requests or
        its oldest request is ``deadline_s`` old — the knob trades per-
        request latency against batch efficiency. Use ``submit`` +
        ``result`` from any thread; ``stop()`` drains and joins.

        Finished results wait in a bounded buffer (``result_cap``); if a
        client never collects (fire-and-forget submits, timed-out
        ``result`` calls), the oldest uncollected results are evicted
        instead of leaking point clouds forever.
        """
        if self._worker is not None:
            raise RuntimeError("background worker already running")
        self._deadline_s = float(deadline_s)
        self._done_cap = max(int(result_cap), 1)
        self._stop_flag = False
        self._worker_dead = False
        self._restarts = 0
        self.stats.g_worker_alive.set(1)
        self._worker = threading.Thread(target=self._worker_main, daemon=True,
                                        name="gnn-serve-worker")
        self._worker.start()

    def stop(self):
        """Stop the worker after draining everything still queued.

        NEVER strands a ``result()`` waiter: anything the worker could not
        drain (it crashed, died beyond its restart budget, or a submit
        raced the final drain) is resolved as a ``Result.error("server
        stopped ...")`` and waiters are notified.
        """
        if self._worker is None:
            return
        with self._cond:
            self._stop_flag = True
            self._cond.notify_all()
        self._worker.join()
        self._worker = None
        self.stats.g_worker_alive.set(0)
        # the graceful path drained everything; this catches the crashed /
        # dead-worker paths and submit-vs-final-drain races
        self._fail_pending("server stopped with this request unserved")

    def _fail_pending(self, reason: str):
        """Resolve every queued + in-flight request as an error Result and
        wake all waiters (worker crash / dead server / stop races)."""
        with self._cond:
            orphans = list(self._inflight)
            self._inflight = []
            for n in sorted(self._queues):
                q = self._queues[n]
                while q:
                    orphans.append(q.popleft())
            for req in orphans:
                self._done[req.request_id] = self._reject(
                    req, 0, reason, np.zeros((0, 3), np.float32), True)
            self.stats.g_queue_depth.set(0)
            if orphans:
                self._cond.notify_all()

    def health(self) -> dict:
        """Liveness/backlog snapshot for monitors (also exported as the
        ``serve_worker_alive`` / ``serve_queue_depth`` /
        ``serve_last_flush_timestamp`` gauges)."""
        with self._cond:
            depth = sum(len(q) for q in self._queues.values())
            inflight = len(self._inflight)
            worker = self._worker
            dead = self._worker_dead
            quarantined = sorted(self._quarantined)
        last_flush = self.stats.g_last_flush.value
        with self.stats.lock:
            errs = {name: getattr(self.stats, name)
                    for name in self.stats._RESILIENCE}
        return {
            "worker_alive": bool(worker is not None and worker.is_alive()
                                 and not dead),
            "worker_dead": dead,
            "queue_depth": depth,
            "inflight": inflight,
            "quarantined_buckets": quarantined,
            "last_flush_age_s": (time.time() - last_flush
                                 if last_flush else None),
            **errs,
        }

    def result(self, request_id: int, timeout: Optional[float] = None
               ) -> Result:
        """Block until the background worker finishes ``request_id``."""
        t0 = time.perf_counter()
        deadline = None if timeout is None else t0 + timeout
        with self._cond:
            self._waiting.add(request_id)     # shield from buffer eviction
            try:
                while request_id not in self._done:
                    rem = None if deadline is None else \
                        deadline - time.perf_counter()
                    if rem is not None and rem <= 0:
                        raise TimeoutError(f"request {request_id} not done "
                                           f"within {timeout}s")
                    self._cond.wait(timeout=rem)
                out = self._done.pop(request_id)
            finally:
                self._waiting.discard(request_id)
        if self.telemetry.enabled:
            self.telemetry.tracer.record_span(
                "result", t0, time.perf_counter(),
                trace_id=f"req-{request_id}")
        return out

    # ------------------------------------------------------------- rollouts

    def rollout_engine(self, **kw):
        """The server's transient-rollout engine (lazily constructed).

        One engine per server: it shares the bucket ladder, calibration
        caches, request-id space, telemetry registry and resilience knobs
        (see ``repro.launch.rollout``). Keyword overrides (``slots``,
        ``steps_per_flush``) apply only on first construction.
        """
        if self._rollout is None:
            from repro.launch.rollout import RolloutEngine
            self._rollout = RolloutEngine(self, **kw)
        return self._rollout

    def submit_rollout(self, verts: np.ndarray, faces: np.ndarray,
                       n_points: Optional[int] = None, *, steps: int = 1,
                       **kw) -> int:
        """Enqueue a T-step rollout; returns its id (see
        ``RolloutEngine.submit``). Collect with ``rollout_result``."""
        return self.rollout_engine().submit(verts, faces, n_points,
                                            steps=steps, **kw)

    def rollout_result(self, rollout_id: int):
        """Drive the engine until ``rollout_id`` resolves; returns its
        ``RolloutResult``."""
        return self.rollout_engine().result(rollout_id)

    def rollout(self, verts: np.ndarray, faces: np.ndarray,
                n_points: Optional[int] = None, *, steps: int = 1, **kw):
        """Synchronous convenience: submit one rollout and drive it to
        completion. Single-shot serving is exactly ``steps=1`` from a zero
        state (bit-equal under the default config — pinned in tests)."""
        rid = self.submit_rollout(verts, faces, n_points, steps=steps, **kw)
        return self.rollout_result(rid)

    def _worker_main(self):
        """Worker supervisor: restart a crashed ``_serve_loop`` with capped
        exponential backoff; past the restart budget mark the server dead.

        Either way no waiter hangs: a crash resolves every queued and
        in-flight request as an error Result (``_fail_pending``) before
        the loop restarts, and a dead server resolves future submits
        immediately (see ``submit``).
        """
        backoff = max(float(self.cfg.worker_backoff_s), 1e-3)
        cap = max(float(self.cfg.worker_backoff_max_s), backoff)
        while True:
            try:
                self._serve_loop()
                return                         # graceful stop() drain
            except BaseException as e:
                self.stats.bump("worker_crashes")
                log.error("serve worker crashed: %r", e)
                self._fail_pending(f"server worker crashed: {e!r}")
                with self._cond:
                    if self._stop_flag:
                        return
                    self._restarts += 1
                    if self._restarts > self.worker_max_restarts:
                        # give up: dead-server mode (submits resolve to
                        # errors immediately — still nobody hangs)
                        self._worker_dead = True
                        self.stats.g_worker_alive.set(0)
                        self._cond.notify_all()
                        log.error(
                            "serve worker exceeded %d restarts; server is "
                            "dead until restarted", self.worker_max_restarts)
                        return
                self.stats.bump("worker_restarts")
                log.warning("restarting serve worker (attempt %d/%d) after "
                            "%.2fs backoff", self._restarts,
                            self.worker_max_restarts, backoff)
                time.sleep(backoff)
                backoff = min(backoff * 2.0, cap)

    def _publish(self, results: List[Result]):
        """Land finished results in the buffer and wake waiters."""
        with self._stage("publish", True, results=len(results)), self._cond:
            for r in results:
                self._done[r.request_id] = r
            self._inflight = []
            # evict oldest UNWAITED results beyond the cap — a result
            # someone is blocked on must survive until they collect it
            for rid in list(self._done):
                if len(self._done) <= self._done_cap:
                    break
                if rid not in self._waiting:
                    self._done.pop(rid)
            self.stats.g_queue_depth.set(
                sum(len(q) for q in self._queues.values()))
            self.stats.g_last_flush.set(time.time())
            self._cond.notify_all()

    def _await_plan(self):
        """Block until work is due, then pop it: ``(plan, expired)``, or
        None once stopped with nothing left. Caller holds ``_cond``."""
        while True:
            plan, expired = self._drain_plan(ready_only=not self._stop_flag)
            if plan or expired:
                # requests leave the queues here; until their results are
                # published they are "in flight" — a crash between drain
                # and publish resolves them via _fail_pending
                self._inflight = [req for _, batch in plan for req in batch]
                return plan, expired
            if self._stop_flag:
                return None
            # sleep until the oldest pending request would trip the flush
            # deadline, or the earliest per-request deadline would expire
            # (or a submit/stop notification)
            now = time.perf_counter()
            oldest = min((q[0].t_submit for q in self._queues.values() if q),
                         default=None)
            wakes = []
            if oldest is not None:
                wakes.append(self._deadline_s - (now - oldest))
            wakes.extend(r.deadline - now
                         for q in self._queues.values() for r in q
                         if r.deadline is not None)
            wait = max(min(wakes), 1e-4) if wakes else None
            self._cond.wait(timeout=wait)

    def _serve_loop(self):
        while True:
            faults.fire("serve.worker")        # chaos: worker crash
            with self._stage("drain", True), self._cond:
                work = self._await_plan()
            if work is None:
                return
            plan, expired = work
            results = [self._timeout_result(n, req) for n, req in expired]
            # per-item errors become error Results inside _run_plan; the
            # outer except is a last resort so an infrastructural failure
            # still cannot kill the thread and hang every waiter
            try:
                results += self._run_plan(plan, self.async_flush,
                                          errors_as_results=True)
            except Exception as e:
                results += [self._reject(req, n, f"serving error: {e!r}",
                                         np.zeros((0, 3), np.float32), True)
                            for n, batch in plan for req in batch]
            self._publish(results)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--buckets", default="512,1024",
                    help="comma-separated static ladder, or 'auto' to "
                    "derive buckets from traffic (autoscaler)")
    ap.add_argument("--max-live-buckets", type=int, default=None,
                    help="compiled-program cache bound for --buckets auto "
                    "(cold buckets are LRU-evicted beyond it)")
    ap.add_argument("--bucket-granularity", type=int, default=None,
                    help="auto bucket sizes round up to this multiple")
    ap.add_argument("--refit-every", type=int, default=None,
                    help="submits between quantile ladder refits (auto)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--knn-impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--agg-impl", default=None,
                    choices=["xla", "sorted", "pallas"],
                    help="processor scatter-add implementation "
                    "(default: the config's, i.e. 'xla')")
    ap.add_argument("--sync", action="store_true",
                    help="disable the async double-buffered flush")
    ap.add_argument("--ckpt", default=None,
                    help="serve trained weights + normalizer stats from a "
                    "launch.train checkpoint")
    ap.add_argument("--compile-cache", default=None,
                    help="persistent XLA compilation cache directory "
                    "(ignored when $JAX_COMPILATION_CACHE_DIR is set; "
                    "default <checkout>/.jax_cache): recompiles of "
                    "previously-seen bucket programs become disk loads "
                    "across restarts / ladder growth / eviction")
    ap.add_argument("--save-artifact", default=None,
                    help="after serving, freeze the adapted server (ladder, "
                    "histogram, calibrated specs, AOT executables) into "
                    "this deploy-artifact file")
    ap.add_argument("--artifact", default=None,
                    help="restore the server from a deploy artifact "
                    "(GNNServer.from_artifact): first request served with "
                    "zero XLA compiles")
    ap.add_argument("--shard-devices", type=int, default=1,
                    help="split each request across this many devices "
                    "(requires that many jax devices, e.g. via "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the span tracer + profiler annotations")
    ap.add_argument("--trace-dir", default=None,
                    help="export trace.jsonl / trace_chrome.json / "
                    "metrics.prom / metrics.json here on exit "
                    "(implies --telemetry)")
    ap.add_argument("--profile", action="store_true",
                    help="additionally capture a full jax.profiler trace "
                    "under <trace-dir>/jax_profile")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission control: bound the pending queue; "
                    "overflow is shed per --shed-policy (0 = unbounded)")
    ap.add_argument("--shed-policy", default=None,
                    choices=["reject", "block"],
                    help="what to do with submits past --max-queue-depth: "
                    "reject (immediate error Result) or block the producer")
    ap.add_argument("--request-timeout", type=float, default=None,
                    help="per-request deadline in seconds; requests that "
                    "wait longer are dropped before any device work and "
                    "resolve to an error Result (0 = no deadline)")
    ap.add_argument("--rollout-steps", type=int, default=0,
                    help="serve the demo traffic as T-step autoregressive "
                    "rollouts through the prefill/insert/generate engine "
                    "(0 = classic single-shot serving)")
    ap.add_argument("--rollout-slots", type=int, default=None,
                    help="concurrent rollouts per bucket slot table "
                    "(default cfg.rollout_slots)")
    ap.add_argument("--steps-per-flush", type=int, default=None,
                    help="physics steps per jitted generate flush "
                    "(default cfg.rollout_steps_per_flush)")
    ap.add_argument("--state-feats", action="store_true",
                    help="feed the field state back into the node features "
                    "(rollout_state_feats; requires params sized for it)")
    ap.add_argument("--integrator", default=None,
                    choices=["direct", "residual"],
                    help="rollout state integrator (default: the config's)")
    ap.add_argument("--rollout-timeout", type=float, default=None,
                    help="per-rollout end-to-end deadline in seconds "
                    "(0 = none)")
    args = ap.parse_args()
    compile_cache.enable(args.compile_cache)

    cfg = GNNConfig()
    if args.reduced:
        cfg = cfg.reduced()
    if args.telemetry or args.trace_dir:
        cfg = cfg.replace(telemetry=True, trace_dir=args.trace_dir or "",
                          profile_capture=args.profile)
    if args.max_live_buckets is not None:
        cfg = cfg.replace(max_live_buckets=args.max_live_buckets)
    if args.bucket_granularity is not None:
        cfg = cfg.replace(bucket_granularity=args.bucket_granularity)
    if args.refit_every is not None:
        cfg = cfg.replace(bucket_refit_every=args.refit_every)
    if args.max_queue_depth is not None:
        cfg = cfg.replace(max_queue_depth=args.max_queue_depth)
    if args.shed_policy is not None:
        cfg = cfg.replace(shed_policy=args.shed_policy)
    if args.request_timeout is not None:
        cfg = cfg.replace(request_timeout_s=args.request_timeout)
    if args.state_feats:
        cfg = cfg.replace(rollout_state_feats=True)
    if args.integrator is not None:
        cfg = cfg.replace(rollout_integrator=args.integrator)
    if args.rollout_slots is not None:
        cfg = cfg.replace(rollout_slots=args.rollout_slots)
    if args.steps_per_flush is not None:
        cfg = cfg.replace(rollout_steps_per_flush=args.steps_per_flush)
    if args.rollout_timeout is not None:
        cfg = cfg.replace(rollout_timeout_s=args.rollout_timeout)
    auto = args.buckets.strip().lower() == "auto"
    buckets = "auto" if auto else \
        tuple(int(b) for b in args.buckets.split(","))
    kw = dict(max_batch=args.max_batch, knn_impl=args.knn_impl,
              agg_impl=args.agg_impl, shard_devices=args.shard_devices,
              async_flush=not args.sync)
    if args.artifact:
        server = GNNServer.from_artifact(args.artifact, cfg=None,
                                         agg_impl=args.agg_impl,
                                         async_flush=not args.sync)
        auto = server.auto
        print(f"restored deploy artifact {args.artifact}: "
              f"buckets {list(server.ladder())}, "
              f"{len(server._aot)} AOT executables")
    elif args.ckpt:
        server = GNNServer.from_checkpoint(args.ckpt, cfg, buckets, **kw)
        print(f"loaded checkpoint {args.ckpt}")
    else:
        server = GNNServer(cfg, buckets, **kw)
    t0 = time.perf_counter()
    if not args.artifact:
        server.warmup()
        if auto:
            print("autoscaling buckets: ladder derived from traffic "
                  "(no warmup compiles)")
        else:
            rep = server.stats.report()
            print(f"warmup ({len(buckets)} buckets): "
                  f"{time.perf_counter() - t0:.1f}s, "
                  f"{rep['bucket_compiles']} compiles, "
                  f"{rep['cache_loads']} compile-cache loads")

    rng = np.random.default_rng(1)
    req_sizes = (128, 192, 256) if auto else buckets
    reqs = []
    for i in range(args.requests):
        verts, faces = geo.car_surface(geo.sample_params(i))
        reqs.append((verts, faces, int(rng.choice(req_sizes))))
    if args.rollout_steps > 0:
        server.rollout_engine()               # construct before timing
        with server.telemetry.capture():
            t_roll = time.perf_counter()
            rids = [server.submit_rollout(v, f, n,
                                          steps=args.rollout_steps)
                    for v, f, n in reqs]
            rollouts = [server.rollout_result(rid) for rid in rids]
            dt = time.perf_counter() - t_roll
        done = sum(r.steps_done for r in rollouts)
        errs = sum(1 for r in rollouts if r.error)
        print(f"rolled out {len(rollouts)} geometries x "
              f"{args.rollout_steps} steps ({done} total) in {dt:.2f}s | "
              f"{done / max(dt, 1e-9):.1f} steps/s | {errs} errors")
        for r in rollouts[:3]:
            cp = r.fields[:, 0]
            print(f"  rollout {r.rollout_id}: bucket {r.bucket}, "
                  f"steps {r.steps_done}/{r.steps}, "
                  f"cp range [{cp.min():.2f}, {cp.max():.2f}]")
        if args.trace_dir:
            paths = server.telemetry.export()
            print("telemetry artifacts: " +
                  ", ".join(sorted(paths.values())))
        if args.save_artifact:
            info = server.save_artifact(args.save_artifact)
            print(f"deploy artifact -> {info['path']} "
                  f"(buckets {info['buckets']}, AOT {info['aot_buckets']})")
        return
    with server.telemetry.capture():
        results = server.serve(reqs)
    rep = server.stats.report()
    print(f"served {rep['requests']} requests | p50 {rep['p50_ms']:.1f} ms | "
          f"p95 {rep['p95_ms']:.1f} ms | mean batch {rep['mean_batch']:.1f} | "
          f"{rep['throughput_rps']:.1f} req/s")
    for stage, s in rep["stages"].items():
        print(f"  stage {stage:<12} n={s['count']:<4} "
              f"mean {s['mean_ms']:.2f} ms  p95 {s['p95_ms']:.2f} ms  "
              f"total {s['total_s']:.3f} s")
    if args.trace_dir:
        paths = server.telemetry.export()
        print("telemetry artifacts: " +
              ", ".join(sorted(paths.values())))
    if auto:
        print(f"auto ladder {list(server.ladder())} | "
              f"hits {rep['bucket_hits']} misses {rep['bucket_misses']} "
              f"evictions {rep['bucket_evictions']} "
              f"compiles {rep['bucket_compiles']} "
              f"cache loads {rep['cache_loads']} "
              f"grown {rep['grown_buckets']} | "
              f"padding waste {rep['padding_waste_frac']:.1%}")
    if args.artifact:
        print(f"cold start: compiles {rep['bucket_compiles']} "
              f"cache loads {rep['cache_loads']} "
              f"calibrations {rep['bucket_calibrations']}")
    if args.save_artifact:
        info = server.save_artifact(args.save_artifact)
        print(f"deploy artifact -> {info['path']} "
              f"(buckets {info['buckets']}, AOT {info['aot_buckets']})")
    for r in results[:3]:
        cp = r.fields[:, 0]
        print(f"  req {r.request_id}: bucket {r.bucket}, "
              f"cp range [{cp.min():.2f}, {cp.max():.2f}]")


if __name__ == "__main__":
    main()
