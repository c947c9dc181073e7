"""Serving hot-path latency/throughput bench -> BENCH_serve.json.

Measures the two serving-performance levers this repo ships:

  flush   sync (sample -> dispatch -> block, one batch at a time) vs the
          async double-buffered flush (host sampling of batch i+1 overlaps
          the in-flight XLA call of batch i). Steady-state throughput and
          per-request p50/p95, plus the cold first request (includes the
          bucket's one-time compile).
  agg     processor scatter-add implementations inside the jitted
          points->prediction pipeline: 'xla' (plain segment_sum), 'sorted'
          (device argsort once per graph + indices_are_sorted reduce),
          'pallas' (sorted block packing + one-hot-MXU kernel; the Pallas
          interpreter on the CPU, so its time there is NOT TPU performance).
          Output parity vs 'xla' is recorded alongside the timings.
  autoscale
          nonstationary request-size traffic (small-resolution phase, then
          a shift to large requests) through a peak-provisioned static
          ladder vs the traffic-derived auto ladder (``bucket_sizes=
          "auto"``): padding waste and p50/p95/p99 latency for the cold
          (adaptation, on-demand compiles — the p99 during ladder growth)
          and warm passes, plus the compiled-program cache counters.
          Asserts auto is no worse than static on padding waste.
  sharded_autoscale
          the same nonstationary stream served under shard_map with
          ``--shard-devices`` shards per program (bucketized ShardSpecs,
          per-bucket halo calibration, cross-request packing). Runs in a
          subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_
          count=N`` (device count locks at first jax init). Records
          padding waste — which now includes replayed pack lanes — and
          warm p50/p95 per ladder; asserts auto <= static waste.
  coldstart
          process-restart latency (``time_to_first_result_s`` = server
          construction/restore + first served request, measured in a fresh
          subprocess after imports) three ways: a truly fresh server
          (compile cache off, compiles everything), a fresh server with a
          WARM persistent compilation cache (re-traces, loads executables
          from disk), and a server restored from a deploy artifact
          (``GNNServer.from_artifact``: zero compiles, zero
          recalibration). Asserts the artifact restore is >= 3x faster
          than the fresh cold start and compiles nothing. The deployment
          is built in a child too and the children run one at a time:
          only one process can hold an accelerator, so this process stays
          off JAX until they are done.

Requests use a densely tessellated geometry (``--nu/--nv``; default ~260k
triangles, the realistic STL regime) so host surface sampling is a real
fraction of the request cost — that is precisely the work the async flush
hides. Latencies are measured submit->result with all requests enqueued up
front, so they include queue wait: p50 reflects batching delay, throughput
reflects the pipeline. CPU-functional numbers, not TPU numbers.

Usage:
  PYTHONPATH=../src python bench_serve.py [--smoke] [--json BENCH_serve.json]

Emits CSV rows (name,us,derived) like the other benches; ``--json`` writes
the machine-readable record.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from common import emit

from repro.ckpt import compile_cache
from repro.configs.base import GNNConfig
from repro.data import geometry as geo
from repro.launch.serve_gnn import GNNServer


def _requests(n_requests: int, bucket: int, nu: int, nv: int):
    reqs = []
    for i in range(n_requests):
        verts, faces = geo.car_surface(geo.sample_params(i), nu=nu, nv=nv)
        reqs.append((verts, faces, bucket))
    return reqs


def _steady_run(server: GNNServer, reqs, async_mode: bool) -> dict:
    """One full drain with fresh stats; returns the stats report + results."""
    server.stats.reset()
    for verts, faces, n in reqs:
        server.submit(verts, faces, n)
    results = server.flush(async_mode=async_mode)
    rep = server.stats.report()
    rep["results"] = results
    return rep


def bench_flush_modes(cfg, reqs, bucket, max_batch, reference, reps, rows,
                      report):
    """Cold first request, then sync-vs-async steady state on one server."""
    server = GNNServer(cfg, (bucket,), max_batch=max_batch,
                       reference=reference, check_requests=False)
    # cold: very first request compiles the bucket's program
    t0 = time.perf_counter()
    [cold_res] = server.serve([reqs[0]])
    cold_s = time.perf_counter() - t0
    assert np.isfinite(cold_res.fields).all()
    rows.append((f"serve_cold_b{bucket}", cold_s * 1e6, "includes compile"))
    report["flush"] = {"cold_first_request_ms": cold_s * 1e3}

    best = {}
    for rep_i in range(reps):
        for mode in (False, True):
            r = _steady_run(server, reqs, async_mode=mode)
            key = "async" if mode else "sync"
            if key not in best or r["throughput_rps"] > \
                    best[key]["throughput_rps"]:
                best[key] = r
    for key in ("sync", "async"):
        r = best[key]
        rows.append((f"serve_{key}_p50_b{bucket}", r["p50_ms"] * 1e3,
                     f"p95={r['p95_ms']:.1f}ms"))
        rows.append((f"serve_{key}_rps_b{bucket}", 0.0,
                     f"{r['throughput_rps']:.2f}req/s"))
        report["flush"][key] = {
            "p50_ms": r["p50_ms"], "p95_ms": r["p95_ms"],
            "throughput_rps": r["throughput_rps"],
            "mean_batch": r["mean_batch"],
            # per-stage breakdown (repro.telemetry histograms behind
            # ServerStats): where a request's wall time actually goes
            "stages": r["stages"],
        }
    speedup = best["async"]["throughput_rps"] / \
        max(best["sync"]["throughput_rps"], 1e-9)
    report["flush"]["async_throughput_speedup"] = speedup
    rows.append((f"serve_async_speedup_b{bucket}", 0.0,
                 f"{speedup:.3f}x over sync"))
    # (async == sync output parity on identical request ids is pinned by
    # tests/test_serve_gnn.py::test_async_flush_matches_sync_exactly; the
    # steady-state runs here deliberately use fresh request ids per run)


def bench_agg_impls(cfg, reqs, bucket, max_batch, reference, impls, rows,
                    report):
    """Same request stream through one server per agg_impl; parity vs xla."""
    report["agg"] = {}
    fields_by_impl = {}
    for impl in impls:
        server = GNNServer(cfg, (bucket,), max_batch=max_batch,
                           reference=reference, check_requests=False,
                           agg_impl=impl, seed=0)
        t0 = time.perf_counter()
        server.warmup()
        warmup_s = time.perf_counter() - t0
        r = _steady_run(server, reqs, async_mode=True)
        fields_by_impl[impl] = {x.request_id: x.fields for x in r["results"]}
        diff = 0.0
        if impl != "xla" and "xla" in fields_by_impl:
            ref = fields_by_impl["xla"]
            diff = max(float(np.abs(ref[k] - fields_by_impl[impl][k]).max())
                       for k in ref)
        rows.append((f"agg_{impl}_p50_b{bucket}", r["p50_ms"] * 1e3,
                     f"warmup={warmup_s:.1f}s "
                     f"rps={r['throughput_rps']:.2f} "
                     f"max_abs_diff_vs_xla={diff:.2e}"))
        report["agg"][impl] = {
            "warmup_compile_s": warmup_s,
            "p50_ms": r["p50_ms"], "p95_ms": r["p95_ms"],
            "throughput_rps": r["throughput_rps"],
            "max_abs_diff_vs_xla": diff,
        }
        if impl != "xla":
            assert diff < 1e-4, f"agg_impl={impl} diverged from xla: {diff}"


def _autoscale_run(cfg, reference, max_batch, smoke, shard_devices=1):
    """Nonstationary-traffic core shared by the unsharded and sharded
    autoscale scenarios: static peak-provisioned ladder vs the auto ladder
    over the identical two-phase stream (cold adaptation pass + warm pass).
    Returns the machine-readable record and asserts auto <= static waste.
    """
    g = 32 if smoke else 64
    small, big = (96, 224) if smoke else (192, 448)
    n_phase = 4 if smoke else 12
    rng = np.random.default_rng(0)
    sizes = [int(small - rng.integers(0, g)) for _ in range(n_phase)] + \
            [int(big - rng.integers(0, g)) for _ in range(n_phase)]
    verts, faces = reference
    reqs = [(verts, faces, n) for n in sizes]
    peak = ((max(sizes) + g - 1) // g) * g
    acfg = cfg.replace(bucket_granularity=g, bucket_quantiles=(0.5, 0.9),
                       bucket_refit_every=max(4, n_phase // 2),
                       max_live_buckets=4)
    out = {
        "traffic": {"sizes": sizes, "phases": [small, big],
                    "granularity": g, "static_ladder": [peak]},
        "shard_devices": int(shard_devices),
    }
    waste = {}
    for name, ladder in (("static", (peak,)), ("auto", "auto")):
        server = GNNServer(acfg, ladder, max_batch=max_batch,
                           reference=reference, check_requests=False,
                           seed=0, shard_devices=shard_devices)
        cold = _steady_run(server, reqs, async_mode=True)
        warm = _steady_run(server, reqs, async_mode=True)
        waste[name] = warm["padding_waste_frac"]
        out[name] = {
            "ladder": list(server.ladder()),
            # cold pass p99 IS the p99-during-ladder-growth: the tail
            # request pays the on-demand calibrate+compile
            "cold": {k: cold[k] for k in
                     ("p50_ms", "p95_ms", "p99_ms", "throughput_rps",
                      "padding_waste_frac", "bucket_compiles",
                      "cache_loads", "grown_buckets")},
            "warm": {k: warm[k] for k in
                     ("p50_ms", "p95_ms", "p99_ms", "throughput_rps",
                      "padding_waste_frac", "bucket_hits", "bucket_misses",
                      "bucket_evictions", "bucket_compiles")},
        }
        for r in cold["results"] + warm["results"]:
            assert r.error is None and np.isfinite(r.fields).all()
    # the autoscaler's reason to exist: resolution-matched buckets waste
    # (far) fewer padded points than peak provisioning on shifting traffic
    assert waste["auto"] <= waste["static"] + 1e-9, waste
    return out


def bench_autoscale(cfg, reference, max_batch, smoke, rows, report):
    """Nonstationary request-size traffic: autoscaling vs static ladder.

    Two traffic phases — small-resolution requests, then a shift to large
    ones (the regime an operator must provision a static ladder for up
    front). The static baseline is a single peak-provisioned bucket; the
    auto server starts with an EMPTY ladder and derives buckets from the
    stream (growth on oversize, quantile refits, LRU program eviction).
    Both servers see the identical stream twice: the first pass is the
    cold/adaptation pass (includes on-demand compiles), the second is
    steady state. Records padding waste (computed-but-unrequested points /
    computed points) and p50/p95 latency for each.
    """
    out = _autoscale_run(cfg, reference, max_batch, smoke)
    report["autoscale"] = out
    for name in ("static", "auto"):
        warm = out[name]["warm"]
        rows.append((f"autoscale_{name}_warm_p95", warm["p95_ms"] * 1e3,
                     f"waste={warm['padding_waste_frac']:.1%} "
                     f"rps={warm['throughput_rps']:.2f} "
                     f"ladder={out[name]['ladder']}"))
    rows.append(("autoscale_waste_ratio", 0.0,
                 f"auto={out['auto']['warm']['padding_waste_frac']:.1%} vs "
                 f"static={out['static']['warm']['padding_waste_frac']:.1%}"))


def _sharded_child(args):
    """Run the autoscale traffic with ``shard_devices`` shards in THIS
    process (the parent forced the host device count via XLA_FLAGS before
    jax initialized). Emits one ``SHARDED_JSON {...}`` line."""
    verts, faces = geo.car_surface(geo.sample_params(0), nu=args.nu,
                                   nv=args.nv)
    cfg = GNNConfig().reduced()
    out = _autoscale_run(cfg, (verts, faces), args.max_batch, args.smoke,
                         shard_devices=args.shard_devices)
    print("SHARDED_JSON " + json.dumps(out))


def bench_sharded_autoscale(max_batch, nu, nv, shard_devices, smoke, rows,
                            report):
    """Autoscaling under shard_map: the same nonstationary stream served
    with ``shard_devices`` shards per program (bucketized ShardSpecs +
    cross-request packing). Runs in a subprocess because the forced host
    device count must be set before jax initializes. Records padding waste
    (including replayed pack lanes) and warm p95 per ladder; asserts the
    auto ladder wastes no more than the peak-provisioned static one.
    """
    cmd = [sys.executable, os.path.abspath(__file__), "--sharded-child",
           "--shard-devices", str(shard_devices),
           "--max-batch", str(max_batch), "--nu", str(nu), "--nv", str(nv)]
    if smoke:
        cmd.append("--smoke")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={shard_devices}").strip()
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, \
        f"sharded autoscale child failed:\n{proc.stdout}\n{proc.stderr}"
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SHARDED_JSON ")][-1]
    out = json.loads(line.split(" ", 1)[1])
    report["sharded_autoscale"] = out
    waste = {}
    for name in ("static", "auto"):
        warm = out[name]["warm"]
        waste[name] = warm["padding_waste_frac"]
        rows.append((f"sharded_autoscale_{name}_warm_p95",
                     warm["p95_ms"] * 1e3,
                     f"P={shard_devices} "
                     f"waste={warm['padding_waste_frac']:.1%} "
                     f"rps={warm['throughput_rps']:.2f} "
                     f"ladder={out[name]['ladder']}"))
    # the child already asserted this; re-check the parsed record so a
    # protocol regression cannot silently drop the contract
    assert waste["auto"] <= waste["static"] + 1e-9, waste
    rows.append(("sharded_autoscale_waste_ratio", 0.0,
                 f"auto={waste['auto']:.1%} vs static={waste['static']:.1%}"))


def bench_overload(cfg, reference, max_batch, smoke, rows, report):
    """Burst overload through the background worker: bounded admission
    control (``max_queue_depth`` + reject shedding) vs an uncapped queue.

    All requests are submitted as fast as the producer can; the worker
    drains at device speed. The uncapped server serves everything (tail
    latency grows with the backlog); the admission-controlled server sheds
    the overflow immediately — p99 of the SERVED requests plus the shed
    rate quantify the trade. Every submitted request must terminate in
    exactly one Result either way.
    """
    bucket = 128 if smoke else 256
    n_req = 24 if smoke else 64
    depth = max_batch * 2
    verts, faces = reference
    report["overload"] = {"n_requests": n_req, "max_queue_depth": depth}
    for name, kw in (("uncapped", {}),
                     ("admission", dict(max_queue_depth=depth,
                                        shed_policy="reject"))):
        server = GNNServer(cfg, (bucket,), max_batch=max_batch,
                           reference=reference, check_requests=False,
                           seed=0, **kw)
        server.warmup()
        server.stats.reset()
        server.start(deadline_s=0.002)
        rids = [server.submit(verts, faces, bucket) for _ in range(n_req)]
        results = [server.result(r, timeout=600.0) for r in rids]
        server.stop()
        assert len(results) == n_req          # every request terminated
        served = [r for r in results if r.error is None]
        shed = [r for r in results if r.error is not None]
        assert all("queue full" in r.error for r in shed), \
            [r.error for r in shed][:3]
        rep = server.stats.report()
        shed_rate = len(shed) / n_req
        report["overload"][name] = {
            "served": len(served), "shed": len(shed),
            "shed_rate": shed_rate,
            "served_p50_ms": rep["p50_ms"], "served_p99_ms": rep["p99_ms"],
            "rejected_overload": rep["rejected_overload"],
        }
        rows.append((f"overload_{name}_p99", rep["p99_ms"] * 1e3,
                     f"shed={shed_rate:.1%} served={len(served)}"))
    # the knob's contract: no admission control -> nothing shed; bounded
    # admission under a burst far beyond the bound -> overflow is shed
    assert report["overload"]["uncapped"]["shed"] == 0, report["overload"]
    assert report["overload"]["admission"]["shed"] > 0, report["overload"]


def _coldstart_child(args):
    """Measure time-to-first-result in THIS fresh process (post-import).

    Modes: ``build`` serves once with the persistent compile cache on and
    saves the deploy artifact (the deployment the restarts start from),
    ``fresh`` builds a server from scratch with the cache off, ``warmcache``
    builds one against the cache ``build`` filled, and ``artifact``
    restores ``GNNServer.from_artifact``. Emits one ``COLDSTART_JSON
    {...}`` line the parent parses.
    """
    verts, faces = geo.car_surface(geo.sample_params(0), nu=args.nu,
                                   nv=args.nv)
    bucket = args.bucket
    mode = args.coldstart_child
    if mode == "fresh":
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        compile_cache.enable(args.compile_cache)
    t0 = time.perf_counter()
    if mode == "artifact":
        server = GNNServer.from_artifact(args.artifact_path)
    else:
        server = GNNServer(GNNConfig().reduced(), (bucket,),
                           max_batch=args.max_batch,
                           reference=(verts, faces), check_requests=False)
    [res] = server.serve([(verts, faces, bucket)])
    t_first = time.perf_counter() - t0
    if mode == "build":
        server.save_artifact(args.artifact_path)
    assert res.error is None and np.isfinite(res.fields).all()
    warm = []
    for _ in range(3):
        t1 = time.perf_counter()
        server.serve([(verts, faces, bucket)])
        warm.append(time.perf_counter() - t1)
    rep = server.stats.report()
    print("COLDSTART_JSON " + json.dumps({
        "mode": mode,
        "backend": jax.default_backend(),
        "compile_cache_dir": compile_cache.enabled_dir(),
        "time_to_first_result_s": t_first,
        "warm_p50_s": float(np.median(warm)),
        "bucket_compiles": rep["bucket_compiles"],
        "cache_loads": rep["cache_loads"],
        "bucket_calibrations": rep["bucket_calibrations"],
    }))


def bench_coldstart(bucket, max_batch, nu, nv, compile_cache_dir, rows,
                    report):
    """Restart latency: fresh vs warm-compile-cache vs deploy artifact.

    A ``build`` child makes the deployment (one served request, persistent
    cache populated, artifact saved); then each restart flavor runs in a
    child of its own, one after another, so jit caches, tracing and
    backend state are genuinely cold. This process never touches JAX.
    ``time_to_first_result_s`` is construction/restore + first request,
    excluding interpreter/import startup (identical across flavors).
    """
    tmp = tempfile.mkdtemp(prefix="bench-coldstart-")
    art = os.path.join(tmp, "deploy.msgpack")

    def child(mode):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--coldstart-child", mode, "--bucket", str(bucket),
               "--max-batch", str(max_batch), "--nu", str(nu),
               "--nv", str(nv), "--artifact-path", art]
        if compile_cache_dir:
            cmd += ["--compile-cache", compile_cache_dir]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=900)
        assert proc.returncode == 0, \
            f"coldstart child {mode} failed:\n{proc.stdout}\n{proc.stderr}"
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("COLDSTART_JSON ")][-1]
        return json.loads(line.split(" ", 1)[1])

    build = child("build")
    fresh = child("fresh")
    warmcache = child("warmcache")
    artifact = child("artifact")

    # contract: a compile-cache restart compiles nothing (disk loads); an
    # artifact restore additionally skips tracing and recalibration
    assert fresh["bucket_compiles"] >= 1, fresh
    assert warmcache["bucket_compiles"] == 0, warmcache
    assert warmcache["cache_loads"] >= 1, warmcache
    assert artifact["bucket_compiles"] == 0, artifact
    assert artifact["bucket_calibrations"] == 0, artifact
    speedup = fresh["time_to_first_result_s"] / \
        max(artifact["time_to_first_result_s"], 1e-9)
    assert speedup >= 3.0, (
        f"artifact restore only {speedup:.2f}x faster than fresh cold "
        f"start (fresh {fresh['time_to_first_result_s']:.2f}s, artifact "
        f"{artifact['time_to_first_result_s']:.2f}s)")

    report["coldstart"] = {
        "build": build, "fresh": fresh, "warm_compile_cache": warmcache,
        "artifact": artifact,
        "artifact_speedup_vs_fresh": speedup,
        "compile_cache_dir": build["compile_cache_dir"],
        "artifact_path": art,
    }
    for name, r in (("fresh", fresh), ("warmcache", warmcache),
                    ("artifact", artifact)):
        rows.append((f"coldstart_{name}_first_result",
                     r["time_to_first_result_s"] * 1e6,
                     f"compiles={r['bucket_compiles']} "
                     f"cache_loads={r['cache_loads']}"))
    rows.append(("coldstart_artifact_speedup", 0.0,
                 f"{speedup:.2f}x over fresh"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (seconds, not minutes)")
    ap.add_argument("--json", default=None,
                    help="write the machine-readable record here")
    ap.add_argument("--bucket", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--nu", type=int, default=None,
                    help="geometry tessellation (faces ~ 2*nu*nv)")
    ap.add_argument("--nv", type=int, default=None)
    ap.add_argument("--reps", type=int, default=2,
                    help="steady-state repetitions (best kept)")
    ap.add_argument("--skip-pallas", action="store_true",
                    help="skip the interpret-mode pallas aggregation run")
    ap.add_argument("--compile-cache", default=None,
                    help="persistent XLA compile-cache dir for the "
                         "coldstart scenario (ignored when "
                         "$JAX_COMPILATION_CACHE_DIR is set; default "
                         "<checkout>/.jax_cache)")
    ap.add_argument("--only", default=None,
                    help="comma-separated scenario subset to run "
                         "(flush,agg,autoscale,sharded_autoscale,coldstart,"
                         "overload); default: all")
    ap.add_argument("--shard-devices", type=int, default=2,
                    help="shard count for the sharded_autoscale scenario "
                         "(forced host devices in a subprocess)")
    ap.add_argument("--coldstart-child", default=None,
                    choices=("build", "fresh", "warmcache", "artifact"),
                    help="internal: run as a coldstart measurement child")
    ap.add_argument("--sharded-child", action="store_true",
                    help="internal: run as the sharded autoscale child")
    ap.add_argument("--artifact-path", default=None,
                    help="internal: deploy artifact for --coldstart-child")
    args = ap.parse_args()

    if args.coldstart_child:
        args.bucket = args.bucket or 256
        args.nu = args.nu or 128
        args.nv = args.nv or 64
        _coldstart_child(args)
        return
    if args.sharded_child:
        args.nu = args.nu or 128
        args.nv = args.nv or 64
        _sharded_child(args)
        return

    bucket = args.bucket or (256 if args.smoke else 512)
    n_req = args.requests or (6 if args.smoke else 16)
    nu = args.nu or (128 if args.smoke else 512)
    nv = args.nv or (64 if args.smoke else 256)
    reps = 1 if args.smoke else args.reps
    impls = ["xla", "sorted"] + ([] if args.skip_pallas else ["pallas"])

    cfg = GNNConfig().reduced()
    reqs = _requests(n_req, bucket, nu, nv)
    reference = (reqs[0][0], reqs[0][1])
    n_faces = len(reqs[0][1])

    rows = []
    report = {
        "config": {
            "bucket": bucket, "max_batch": args.max_batch,
            "requests": n_req, "nu": nu, "nv": nv, "n_faces": n_faces,
            "reduced": True, "smoke": bool(args.smoke),
        },
    }
    all_scenarios = ("flush", "agg", "autoscale", "sharded_autoscale",
                     "coldstart", "overload")
    scenarios = set((args.only or ",".join(all_scenarios)).split(","))
    unknown = scenarios - set(all_scenarios)
    assert not unknown, f"unknown --only scenarios: {sorted(unknown)}"
    # child-process scenarios first, while this process is still off JAX
    # (a process that has touched JAX holds the accelerator)
    if "sharded_autoscale" in scenarios:
        bench_sharded_autoscale(args.max_batch, nu, nv, args.shard_devices,
                                args.smoke, rows, report)
    if "coldstart" in scenarios:
        bench_coldstart(bucket, args.max_batch, nu, nv, args.compile_cache,
                        rows, report)
    if "flush" in scenarios:
        bench_flush_modes(cfg, reqs, bucket, args.max_batch, reference, reps,
                          rows, report)
    if "agg" in scenarios:
        bench_agg_impls(cfg, reqs, bucket, args.max_batch, reference, impls,
                        rows, report)
    if "autoscale" in scenarios:
        bench_autoscale(cfg, reference, args.max_batch, args.smoke, rows,
                        report)
    if "overload" in scenarios:
        bench_overload(cfg, reference, args.max_batch, args.smoke, rows,
                       report)
    if args.smoke and "flush" in scenarios:
        # CI contract: the JSON record carries the per-stage breakdown
        for key in ("sync", "async"):
            stages = report["flush"][key]["stages"]
            assert stages, f"flush[{key}] has no stage breakdown"
            for st, s in stages.items():
                assert {"count", "mean_ms", "p50_ms", "p95_ms",
                        "total_s"} <= set(s), (key, st, s)
            assert any(s["count"] > 0 for s in stages.values()), stages
    report["config"]["backend"] = jax.default_backend()
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
