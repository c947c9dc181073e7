"""Bring-up check: the main path on a TPU at xmgn-drivaer's published widths.

Run from the root of a checkout::

    python chip_smoke.py             # one chip: serve, rollout, cold start,
                                     # train and the Pallas kernels
    python chip_smoke.py --chips 4   # four chips: sharded serving and the
                                     # partition-parallel train step, each
                                     # against its one-device counterpart

Every phase goes through the entry points a user calls (``GNNServer``,
``serve``, ``submit_rollout``, ``save_artifact``/``from_artifact``,
``train_gnn``) with the widths of ``GNNConfig()`` unchanged: hidden 512, 15
message-passing layers, 2-layer MLPs, k=6, 3 nested levels, 21 partitions
and a 15-hop halo. Weights are random from a fixed seed. Outputs are
compared with plain float32 references run under
``jax.default_matmul_precision("highest")``. Each cut from the published
configuration, each error against its bound and each observation is
printed on an earlier line; times are observations of one cold run, not
benchmark numbers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line. Everything runs in this one process: a second process
could not reach the chip this one holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from dataclasses import dataclass
from typing import Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.ckpt import compile_cache  # noqa: E402
from repro.configs.base import GNNConfig  # noqa: E402
from repro.core.graph import relative_edge_features  # noqa: E402
from repro.core.graph_build import (node_input_features,  # noqa: E402
                                    sample_surface)
from repro.core.multiscale import multiscale_edges  # noqa: E402
from repro.data import geometry as geo  # noqa: E402
from repro.data import pipeline as pipe  # noqa: E402
from repro.graphx.pipeline import make_edges_fn  # noqa: E402
from repro.kernels.segment_agg import ops as segops  # noqa: E402
from repro.launch.serve_gnn import GNNServer  # noqa: E402
from repro.launch.sharding import mesh_for_shards, shard_count_for  # noqa
from repro.launch.train import (make_gnn_step_fn, prepare_gnn_batch,  # noqa
                                train_gnn)
from repro.models import meshgraphnet as mgn  # noqa: E402
from repro.optim.adam import AdamConfig, adam_init  # noqa: E402
from repro.telemetry import Telemetry  # noqa: E402


@dataclass(frozen=True)
class Tol:
    max_abs: float
    rel_l2: float


# Bounds against the float32 reference. The programs keep the model's
# float32 arithmetic, but a TPU runs float32 matmuls as bf16 passes unless
# told otherwise, so the bounds come from what a v5e showed, with headroom:
# fields within 0.0201 max-abs and 0.0077 relative L2 of the reference
# (|ref| up to 1.26), the first training loss within 6.1e-4.
FIELD_TOL = Tol(max_abs=0.1, rel_l2=2e-2)
LOSS_RTOL = 1e-2
SEED = 0                                 # server sampling + weight seed


@dataclass(frozen=True)
class Sizes:
    """What is cut from the published configuration (each is printed)."""
    # serving buckets (points per request); 65 536 is the largest whose
    # max_batch=1 program fits one 16 GB chip at full width
    ladder: Tuple[int, ...] = (16384, 65536)
    requests: Tuple[Tuple[int, int], ...] = ((1, 16384), (2, 16384),
                                             (3, 65536))
    rollout_steps: Tuple[int, ...] = (3, 6)
    rollout_slots: int = 2
    # training cloud: nested levels of one sample, partitioned 21 ways;
    # the finest level is about one paper partition (2M / 21 points). The
    # scanned step's program needs ~12 GiB of the chip's 16 GB here.
    train_levels: Tuple[int, ...] = (24576, 49152, 98304)
    train_samples: int = 2               # 1 train + 1 held out
    train_steps: int = 3
    # four chips: shard_map needs a partition count the devices divide
    four_bucket: int = 16384
    four_partitions: int = 20


class SmokeFailure(RuntimeError):
    pass


# Failed checks of the current run. A check that fails is printed and the
# run goes on, so one run on the chip reports every phase; the run raises
# at its end if any failed.
_failed: list = []


def say(msg: str):
    print(msg, flush=True)


def fail(msg: str):
    say(f"FAIL: {msg}")
    _failed.append(msg)


def run_phases(phases):
    """Run each ``(name, fn)`` in order. A phase that raises is a failure;
    the phases after it still run. Raises if any check of the run failed."""
    _failed.clear()
    for name, fn in phases:
        try:
            fn()
        except Exception as e:
            traceback.print_exc()
            fail(f"phase {name} raised {type(e).__name__}: {e}")
        say(f"phase {name} done")
    if _failed:
        raise SmokeFailure(f"{len(_failed)} checks failed: {_failed}")


def observe(name: str, value: float, unit: str):
    say(f"smoke observation (not a benchmark number): {name} = "
        f"{value!r} {unit}")


def compare(label: str, got, want, tol: Tol):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = got - want
    max_abs = float(np.abs(diff).max())
    rel = float(np.linalg.norm(diff) / max(np.linalg.norm(want), 1e-30))
    ok = max_abs <= tol.max_abs and rel <= tol.rel_l2
    say(f"{label}: max_abs_err {max_abs!r} rel_l2_err {rel!r} (bounds "
        f"{tol.max_abs} / {tol.rel_l2}, |ref|max "
        f"{float(np.abs(want).max())!r}) {'ok' if ok else 'FAIL'}")
    if not ok:
        fail(f"{label} outside its bounds")


_FAILURE_COUNTERS = ("quarantined_buckets", "bucket_fallbacks",
                     "nonfinite_results", "rejected_requests",
                     "overflow_requests", "oversize_requests",
                     "timed_out_requests", "worker_crashes")


def check_clean(label: str, srv: GNNServer, results=()) -> dict:
    """No fallback fired, nothing was rejected, no result is an error."""
    rep = srv.stats.report()
    fired = {k: rep[k] for k in _FAILURE_COUNTERS if rep[k]}
    errors = [r.error for r in results if r.error is not None]
    if fired or errors:
        fail(f"{label}: counters {fired}, errors {errors[:3]}")
    return rep


def requests(spec: Sequence[Tuple[int, int]]):
    out = []
    for gseed, n in spec:
        verts, faces = geo.car_surface(geo.sample_params(gseed))
        out.append((verts, faces, n))
    return out


def served_cloud(req, result):
    """The (points, normals) the server sampled for ``result``: sampling is
    deterministic per (server seed, request id)."""
    verts, faces, _ = req
    pts, nrm = sample_surface(verts, faces, result.bucket,
                              np.random.default_rng((SEED,
                                                     result.request_id + 1)))
    if not np.array_equal(pts, result.points):
        raise SmokeFailure(f"request {result.request_id}: resampled cloud "
                           "differs from the served one")
    return pts, nrm


def level_sizes(n: int, n_levels: int = 3):
    return tuple(n // 2 ** (n_levels - 1 - i) for i in range(n_levels))


class Reference:
    """Plain float32 forward: host cKDTree multi-scale edges, numpy
    features and ``meshgraphnet.apply`` with the plain scatter-add, at the
    highest matmul precision. Every cloud is padded to one shape (isolated
    padding nodes, masked padding edges) so the program compiles once."""

    def __init__(self, cfg: GNNConfig, params, clouds):
        self.cfg, self.params = cfg, params
        self.graphs = [self._graph(p, n) for p, n in clouds]
        self.n_nodes = max(len(p) for p, _ in clouds)
        e = max(len(g[0]) for g in self.graphs)
        self.n_edges = -(-e // 4096) * 4096
        self.fn = jax.jit(lambda p, nf, ef, s, r, m: mgn.apply(
            p, cfg, nf, ef, s, r, edge_mask=m, agg_impl="xla"))

    def _graph(self, pts, nrm):
        s, r, _ = multiscale_edges(pts, level_sizes(len(pts)),
                                   self.cfg.k_neighbors)
        return (s, r, node_input_features(pts, nrm, self.cfg.fourier_freqs),
                relative_edge_features(pts, s, r))

    def __call__(self, i: int) -> np.ndarray:
        s, r, nf, ef = self.graphs[i]
        n, e = len(nf), len(s)
        pad_n, pad_e = self.n_nodes - n, self.n_edges - e
        args = (np.pad(nf, ((0, pad_n), (0, 0))),
                np.pad(ef, ((0, pad_e), (0, 0))),
                np.pad(s, (0, pad_e)), np.pad(r, (0, pad_e)),
                np.pad(np.ones(e, np.float32), (0, pad_e)))
        with jax.default_matmul_precision("highest"):
            out = self.fn(self.params, *map(jnp.asarray, args))
        return np.asarray(out)[:n]


# ------------------------------------------------------------ one chip

def serve_phase(cfg: GNNConfig, sizes: Sizes, reqs):
    say(f"cut: serving ladder {sizes.ladder} points per request at "
        "max_batch 1 (paper: 2M-point clouds)")
    srv = GNNServer(cfg, sizes.ladder, max_batch=1, seed=SEED)
    t0 = time.perf_counter()
    srv.warmup()
    observe("serve compile, all buckets", time.perf_counter() - t0, "s")
    t0 = time.perf_counter()
    results = sorted(srv.serve(reqs), key=lambda r: r.request_id)
    observe("serve first results", time.perf_counter() - t0, "s")
    t0 = time.perf_counter()
    warm = srv.serve(reqs)
    observe("serve warm, per request",
            (time.perf_counter() - t0) / len(reqs) * 1e3, "ms")
    rep = check_clean("serve", srv, results + warm)
    programs = rep["bucket_compiles"] + rep["cache_loads"]
    if programs != len(sizes.ladder):
        fail(f"serve built {programs} programs for {len(sizes.ladder)} "
             "buckets")
    clouds = [served_cloud(q, r) for q, r in zip(reqs, results)]
    t0 = time.perf_counter()
    ref = Reference(cfg, srv.params, clouds)
    for i, r in enumerate(results):
        compare(f"serve request {r.request_id} (bucket {r.bucket}) vs "
                "reference", r.fields, ref(i), FIELD_TOL)
    observe("reference edges + forward, all requests",
            time.perf_counter() - t0, "s")
    return srv, results, clouds


def rollout_phase(srv: GNNServer, sizes: Sizes, reqs, results, clouds):
    bucket = sizes.ladder[0]
    say(f"cut: rollouts of {sizes.rollout_steps} steps in bucket {bucket}, "
        f"{sizes.rollout_slots} slots (config: {srv.cfg.rollout_slots})")
    srv.rollout_engine(slots=sizes.rollout_slots)
    small = [(q, r, c) for q, r, c in zip(reqs, results, clouds)
             if r.bucket == bucket]
    t0 = time.perf_counter()
    rids = [srv.submit_rollout(v, f, bucket, steps=t)
            for ((v, f, _), _, _), t in zip(small, sizes.rollout_steps)]
    outs = [srv.rollout_result(rid) for rid in rids]
    observe("rollouts incl. compile", time.perf_counter() - t0, "s")
    for out in outs:
        if (out.error is not None or out.steps_done != out.steps
                or not np.isfinite(out.fields).all()):
            fail(f"rollout {out.rollout_id}: error {out.error!r}, "
                 f"{out.steps_done}/{out.steps} steps or not finite")
        else:
            say(f"rollout {out.rollout_id}: {out.steps_done} steps, finite")
    (verts, faces, n), served, cloud = small[0]
    one = srv.rollout(verts, faces, n, steps=1, cloud=cloud)
    if one.error is not None:
        raise SmokeFailure(f"T=1 rollout: {one.error}")
    compare(f"T=1 rollout vs serve request {served.request_id}",
            one.fields, served.fields, FIELD_TOL)
    check_clean("rollout", srv)


def coldstart_phase(srv: GNNServer, reqs, results, workdir: str):
    os.makedirs(workdir, exist_ok=True)
    path = os.path.join(workdir, "chip_smoke_deploy.msgpack")
    t0 = time.perf_counter()
    info = srv.save_artifact(path)
    observe("save_artifact", time.perf_counter() - t0, "s")
    say(f"artifact: live buckets {info['buckets']}, AOT programs "
        f"{info['aot_buckets']}")
    # XLA:CPU cannot serialize executables, so a CPU rehearsal restores
    # through the compile cache; on the chip every bucket ships one
    if (jax.default_backend() == "tpu"
            and sorted(info["aot_buckets"]) != sorted(srv.ladder())):
        fail(f"artifact lacks AOT programs for live buckets {srv.ladder()}")
    try:
        t0 = time.perf_counter()
        dst = GNNServer.from_artifact(path)
        [res] = dst.serve([reqs[0]])
        observe("restore + first result", time.perf_counter() - t0, "s")
    finally:
        os.remove(path)
    rep = check_clean("cold start", dst, [res])
    say(f"cold start: bucket_compiles {rep['bucket_compiles']}, "
        f"bucket_calibrations {rep['bucket_calibrations']}, cache_loads "
        f"{rep['cache_loads']}")
    if rep["bucket_compiles"] or rep["bucket_calibrations"]:
        fail("artifact restore compiled or recalibrated")
    compare(f"restored server vs source, request {res.request_id}",
            res.fields, results[0].fields, FIELD_TOL)


def train_phase(cfg: GNNConfig, sizes: Sizes):
    tcfg = cfg.replace(levels=sizes.train_levels)
    say(f"cut: training levels {sizes.train_levels} (paper "
        f"{cfg.levels}), {sizes.train_samples} samples, "
        f"{sizes.train_steps} steps; {tcfg.n_partitions} partitions, "
        f"halo {tcfg.halo}")
    tel = Telemetry.from_config(tcfg)
    t0 = time.perf_counter()
    _, losses, (train, _, norm_in, norm_out) = train_gnn(
        tcfg, sizes.train_steps, sizes.train_samples, shard_devices=1,
        telemetry=tel, log_every=1)
    observe("train_gnn incl. data, partitioning and compile",
            time.perf_counter() - t0, "s")
    skipped = tel.metrics.counter("train_nonfinite_steps_total").value
    if skipped or not np.isfinite(losses).all():
        fail(f"training: losses {losses}, {skipped} steps skipped")
    s = train[0]
    g = s.graph
    batch = {"node_feats": norm_in.encode(s.node_feats),
             "edge_feats": g.edge_feats, "senders": g.senders,
             "receivers": g.receivers, "targets": norm_out.encode(s.targets),
             "loss_mask": np.ones(g.n_nodes, np.float32)}
    params0 = mgn.init(jax.random.PRNGKey(0), tcfg)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: mgn.loss_fn(p, tcfg, b,
                                                     agg_impl="xla"))(
            params0, jax.tree_util.tree_map(jnp.asarray, batch)))
    rel = abs(losses[0] - ref) / abs(ref)
    say(f"train: first-step loss {losses[0]!r} vs full-graph reference "
        f"{ref!r}: rel_err {rel!r} (bound {LOSS_RTOL}) "
        f"{'ok' if rel <= LOSS_RTOL else 'FAIL'}; losses {losses}")
    if not rel <= LOSS_RTOL:
        fail("first-step loss outside its bound")


def pallas_phase(cfg: GNNConfig, sizes: Sizes, reqs, results, clouds):
    bucket = sizes.ladder[0]
    n_small = sum(1 for r in results if r.bucket == bucket)
    # the same leading requests get the same request ids, hence clouds
    psrv = GNNServer(cfg, (bucket,), max_batch=1, seed=SEED,
                     knn_impl="pallas", agg_impl="pallas")
    t0 = time.perf_counter()
    pres = sorted(psrv.serve(reqs[:n_small]), key=lambda r: r.request_id)
    observe("pallas serve incl. compile", time.perf_counter() - t0, "s")
    check_clean("pallas", psrv, pres)
    text = psrv._buckets[bucket].infer.lower(
        *psrv._bucket_arg_specs(bucket)).as_text()
    n_calls = text.count("tpu_custom_call")
    say(f"pallas: bucket program lowers with {n_calls} tpu_custom_call "
        "sites")
    if jax.default_backend() == "tpu" and n_calls == 0:
        fail("pallas kernels were not compiled for the chip")
    ms = psrv._calibrate(bucket)
    edges = make_edges_fn(ms)

    @jax.jit
    def dropped(pts):
        s, r, em = edges(pts, ms.n_points)
        return segops.prepare_device(mgn.spread_padding(r, em, ms.n_points),
                                     ms.n_points).n_dropped

    for r, p, (pts, _) in zip(results, pres, clouds):
        compare(f"pallas vs xla, request {r.request_id}", p.fields,
                r.fields, FIELD_TOL)
        n_drop = int(dropped(jnp.asarray(pts)))
        say(f"pallas: request {r.request_id} aggregation overflow "
            f"{n_drop} edges -> scatter-add fallback "
            f"{'FIRED' if n_drop else 'not fired'}")


def run_one_chip(cfg: GNNConfig, sizes: Sizes, workdir: str):
    """Every one-chip phase; ``workdir`` takes the deploy artifact. The
    rollout, cold-start and Pallas phases reuse what serving returned."""
    reqs = requests(sizes.requests)
    s = {}

    def serve():
        s["srv"], s["results"], s["clouds"] = serve_phase(cfg, sizes, reqs)

    run_phases([
        ("serve", serve),
        ("rollout", lambda: rollout_phase(s["srv"], sizes, reqs,
                                          s["results"], s["clouds"])),
        ("cold start", lambda: coldstart_phase(s["srv"], reqs, s["results"],
                                               workdir)),
        ("train", lambda: train_phase(cfg, sizes)),
        ("pallas", lambda: pallas_phase(cfg, sizes, reqs, s["results"],
                                        s["clouds"]))])


# ---------------------------------------------------------- four chips

def sharded_serve_phase(cfg: GNNConfig, sizes: Sizes, n_dev: int):
    bucket = sizes.four_bucket
    reqs = requests([(g, bucket) for g, _ in sizes.requests[:2]])
    say(f"cut: sharded serving bucket {bucket} (a 15-hop halo puts the "
        "whole cloud on every shard at this size)")
    one = GNNServer(cfg, (bucket,), max_batch=1, seed=SEED)
    want = sorted(one.serve(reqs), key=lambda r: r.request_id)
    check_clean("one-device serve", one, want)
    shd = GNNServer(cfg, (bucket,), max_batch=1, seed=SEED,
                    shard_devices=n_dev)
    used = shd._mesh.devices.size
    say(f"sharded serving mesh: {used} devices")
    if used != n_dev:
        fail(f"sharded mesh used {used} of {n_dev} devices")
    t0 = time.perf_counter()
    got = sorted(shd.serve(reqs), key=lambda r: r.request_id)
    observe("sharded serve incl. compile", time.perf_counter() - t0, "s")
    check_clean("sharded serve", shd, got)
    for g, w in zip(got, want):
        compare(f"sharded vs one-device, request {g.request_id}", g.fields,
                w.fields, FIELD_TOL)


def psum_train_phase(cfg: GNNConfig, sizes: Sizes, n_dev: int):
    tcfg = cfg.replace(levels=sizes.train_levels,
                       n_partitions=sizes.four_partitions)
    say(f"cut: training levels {sizes.train_levels}, partitions "
        f"{tcfg.n_partitions} (paper {cfg.n_partitions}: shard_map needs "
        f"a count {n_dev} devices divide)")
    s = pipe.build_sample(tcfg, 0)
    [ps] = pipe.partition_samples(tcfg, [s],
                                  pipe.Normalizer.fit([s.node_feats]),
                                  pipe.Normalizer.fit([s.targets]))
    n_shards = shard_count_for(tcfg.n_partitions)
    mesh = mesh_for_shards(n_shards)
    say(f"partition-parallel mesh: {mesh.devices.size} devices")
    if mesh.devices.size != n_dev:
        fail(f"train mesh used {mesh.devices.size} of {n_dev} devices")
    params = mgn.init(jax.random.PRNGKey(0), tcfg)
    opt_cfg = AdamConfig(total_steps=1)
    out = {}
    for name, m in (("scan", None), ("psum", mesh)):
        step = make_gnn_step_fn(tcfg, opt_cfg, mesh=m)
        t0 = time.perf_counter()
        p, _, loss, gnorm, skipped = step(params, adam_init(params),
                                          *prepare_gnn_batch(ps, m))
        out[name] = (jax.tree_util.tree_map(np.asarray, p), float(loss),
                     float(gnorm), bool(skipped))
        observe(f"{name} train step incl. compile",
                time.perf_counter() - t0, "s")
    (p_s, l_s, g_s, k_s), (p_p, l_p, g_p, k_p) = out["scan"], out["psum"]
    dp = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(p_s), jax.tree_util.tree_leaves(p_p)))
    rl, rg = abs(l_p - l_s) / abs(l_s), abs(g_p - g_s) / abs(g_s)
    say(f"psum vs scan step: loss {l_p!r} vs {l_s!r} (rel {rl!r}), grad "
        f"norm {g_p!r} vs {g_s!r} (rel {rg!r}), max param diff {dp!r} "
        f"(bound {LOSS_RTOL} relative)")
    if k_s or k_p or not (rl <= LOSS_RTOL and rg <= LOSS_RTOL):
        fail("partition-parallel step differs from the scan")


def run_four_chips(cfg: GNNConfig, sizes: Sizes, n_dev: int = 4):
    run_phases([
        ("sharded serve", lambda: sharded_serve_phase(cfg, sizes, n_dev)),
        ("partition-parallel train",
         lambda: psum_train_phase(cfg, sizes, n_dev))])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only sharded serving and the "
                    "partition-parallel train step on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    say(f"device: {dev.device_kind} x{len(devices)}; compile cache at "
        f"{compile_cache.enable()}")
    cfg = GNNConfig()
    say(f"config: hidden {cfg.hidden}, {cfg.n_mp_layers} mp layers, "
        f"mlp_layers {cfg.mlp_layers}, k {cfg.k_neighbors}, "
        f"{len(cfg.levels)} levels, {cfg.n_partitions} partitions, "
        f"halo {cfg.halo}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(cfg, Sizes(), n_dev=4)
    else:
        run_one_chip(cfg, Sizes(), os.path.join(ROOT, "artifacts"))
    observe("whole run", time.perf_counter() - t0, "s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
