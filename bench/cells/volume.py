"""A volume serving cell: X-UNet3D through ``GNNServer``'s
``start``/``submit``/``result``.

Everything but the model and the rate is the serving cell's
(``bench/cells/serve.py``, whose closed loop and stage totals this module
calls): the geometry pool, the one bucket of the traffic's points compiled
before the window, the warm request, ``outstanding`` geometries in flight,
the window that starts and ends at an answer. The weights come from the
seed (``bench/reference_unet.py``); each answer holds the fields of the
whole voxel grid.

``rate`` counts whole service periods on the server: each answer's ready
time is when its request was sent plus the latency the server reports
(sent to harvested), and the rate is the answers after the first ready,
over the time from it to the last. An answer takes seconds, so the window
holds a handful; the worker publishes a plan's answers together, so the
times the client takes them come in bursts, and a rate from those, or
from the window's start, would count a burst's phase.

After the window a seeded sample of its answers is compared with the plain
reference (``bench/reference_unet.py``). The reference rebuilds each
answer's cloud from the server's seed and the request id; the served
cloud must equal it (``cloud_max_err``, the largest coordinate gap). Each
answer is compared on ``check_blocks`` seeded x-blocks, one in each of
that many equal strata of the domain: the reference computes
``CHECK_OWNED`` planes in a window of ``CHECK_HALO`` more planes a side (a
width and offsets the program's slabs do not have). The numbers compared
are ``fields_rms_err`` (the largest root mean square gap of a block's
fields from the reference's), ``cloud_max_err`` and ``missing_answers``;
``bench/run.py`` holds them against the cell's limits.

On a program whose ``GNNServer`` serves no ``unet`` family the cell stops
before any set-up.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, reference_unet, traffic, tracing
from bench.cells import serve

CHECK_OWNED = 24       # planes of one compared block
CHECK_HALO = 28        # the reference's halo: at least the receptive field
                       # (26), a multiple of the pooling alignment (4)


def unet_config(cfg: dict, telemetry: bool):
    """The program's config object for a benchmark config."""
    from repro.configs.base import UNetConfig
    keys = ("in_channels", "out_channels", "base_channels", "depth",
            "blocks_per_level", "kernel_size", "pool", "act",
            "attention_gates", "halo", "n_partitions", "voxel", "sdf_band",
            "sdf_prefix")
    return UNetConfig(name=cfg["name"], grid=tuple(cfg["grid"]),
                      box_lo=tuple(cfg["box_lo"]), telemetry=telemetry,
                      **{k: cfg[k] for k in keys})


def start_server(cfg: dict, mix: dict, seed: int, telemetry: bool):
    """Weights, geometry pool, and a started server with its one bucket
    compiled (or loaded from the compile cache)."""
    from repro.launch.serve_gnn import GNNServer

    if "unet" not in getattr(GNNServer, "FAMILIES", ()):
        raise RuntimeError("this program's GNNServer serves no 'unet' "
                           "family: the volume cell cannot run here")
    points = mix["points"]
    if points > cfg["max_points"]:
        raise ValueError(f"traffic sends {points} points; config "
                         f"{cfg['name']} is cut at {cfg['max_points']}")
    pool = traffic.geometry_pool(mix)
    params = reference_unet.init_params(cfg, seed)
    srv = GNNServer(unet_config(cfg, telemetry), (points,), params=params,
                    max_batch=mix["max_batch"], seed=int(seed),
                    async_flush=True)
    srv.warmup()
    srv.start(deadline_s=mix["deadline_ms"] / 1e3)
    return srv, pool, params


def block_starts(cfg: dict, n_blocks: int, rng) -> List[int]:
    """Seeded starts of ``CHECK_OWNED``-plane blocks, one in each of
    ``n_blocks`` equal strata of the x extent (fewer where the extent holds
    fewer blocks), at multiples of the pooling alignment."""
    X = cfg["grid"][0]
    align = 2 ** (cfg["depth"] - 1)
    n = max(min(n_blocks, X // CHECK_OWNED), 1)
    out = []
    for k in range(n):
        lo = -(-(k * X // n) // align) * align
        hi = min(max((k + 1) * X // n - CHECK_OWNED, lo), X - CHECK_OWNED)
        out.append(int(rng.choice(np.arange(lo, hi + 1, align))))
    return out


def check(cfg: dict, mix: dict, pool, params, seed: int,
          answers: List[serve.Answer], control: bool = False) -> dict:
    """Compare ``check_blocks`` seeded blocks of each of a seeded sample of
    the window's answers with the reference, which rebuilds each answer's
    cloud. Returns ``{numbers, control, checked}`` as ``serve.check``
    does; the control is the reference wholly in bfloat16 (weights, the
    cloud it is featurized from, features and arithmetic) on the same
    blocks, in the program's place for ``fields_rms_err``."""
    missing = sum(1 for a in answers if a.result is None)
    ok = [a for a in answers
          if a.result is not None and a.result.error is None]
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(len(ok), size=min(mix["check_sample"], len(ok)),
                      replace=False) if ok else []
    worst, worst_control, cloud_gap = 0.0, 0.0, 0.0
    for j in sorted(int(p) for p in pick):
        a = ok[j]
        verts, faces = pool[a.pool]
        pts, nrm = reference_unet.sample_cloud(verts, faces, mix["points"],
                                               seed, a.rid)
        served = np.asarray(a.result.points, np.float32)
        gap = (float(np.max(np.abs(served - pts)))
               if served.shape == pts.shape else math.inf)
        serve.say(f"check: request {a.rid} (pool {a.pool}): cloud_max_err "
                  f"{gap!r}")
        cloud_gap = max(cloud_gap, gap)
        for x0 in block_starts(cfg, mix["check_blocks"], rng):
            want = reference_unet.block_fields(params, cfg, pts, nrm, x0,
                                               CHECK_OWNED, CHECK_HALO)
            gap = reference.rms_gap(a.result.fields[x0:x0 + CHECK_OWNED],
                                    want)
            line = (f"check: request {a.rid} planes {x0}..{x0 + CHECK_OWNED}"
                    f" (fields rms {reference.rms_gap(want, 0.0)!r}): "
                    f"fields_rms_err {gap!r}")
            worst = max(worst, gap)
            if control:
                low = reference_unet.block_fields(
                    params, cfg, pts, nrm, x0, CHECK_OWNED, CHECK_HALO,
                    dtype=jnp.bfloat16, cloud_dtype=jnp.bfloat16)
                gap_c = reference.rms_gap(low, want)
                line += f", control {gap_c!r}"
                worst_control = max(worst_control, gap_c)
            serve.say(line)
    numbers = {"fields_rms_err": worst if len(pick) else None,
               "cloud_max_err": cloud_gap if len(pick) else None,
               "missing_answers": missing}
    return {"numbers": numbers,
            "control": (dict(numbers, fields_rms_err=worst_control)
                        if control and len(pick) else None),
            "checked": len(pick)}


class Client:
    """The server as the closed loop drives it, noting on the host clock
    when each request was sent."""

    def __init__(self, srv):
        self.srv = srv
        self.sent: Dict[int, float] = {}

    def submit(self, *args):
        t = time.perf_counter()
        rid = self.srv.submit(*args)
        self.sent[rid] = t
        return rid

    def result(self, rid: int, timeout: Optional[float] = None):
        return self.srv.result(rid, timeout=timeout)


def ready_times(answers: List[serve.Answer], sent: Dict[int, float]
                ) -> List[float]:
    """When each answer was ready on the server, in order: sent plus the
    server's latency (submit to harvest)."""
    return sorted(sent[a.rid] + a.result.latency_s for a in answers
                  if a.result is not None and a.result.error is None)


def period_rate(ready: List[float]) -> Optional[float]:
    """Answers after the first ready one, over the time from it to the
    last; None with fewer than two."""
    if len(ready) < 2:
        return None
    return (len(ready) - 1) / (ready[-1] - ready[0])


def run(cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
        t_process: float, trace_dir: str, control: bool = False) -> dict:
    """Set up, measure, check: what ``bench/run.py`` and the metric readers
    need (see there). Beside the serving cell's keys: ``voxels``, the
    window's ``volume_voxels_owned`` and ``volume_voxels_computed`` server
    counters."""
    from repro.ckpt import compile_cache

    srv, pool, params = start_server(cfg, mix, seed, trace)
    cap = tracing.Capture(trace, trace_dir, mix["trace_s"])
    marks: dict = {}
    voxel_keys = ("volume_voxels_owned", "volume_voxels_computed")

    def on_start():
        marks["setup_s"] = time.perf_counter() - t_process
        marks["stages0"] = serve.stage_totals(srv)
        marks["events0"] = compile_cache.CompileEvents()
        rep = srv.stats.report()
        marks["voxels0"] = [rep[k] for k in voxel_keys]

    try:
        client = Client(srv)
        out = serve.closed_loop(client, pool, mix, seed, seconds, cap,
                                on_start)
    finally:
        srv.stop()
    stages1 = serve.stage_totals(srv)
    misses, hits = marks["events0"].delta()
    programs = stages1["programs"][0] - marks["stages0"]["programs"][0]
    serve.say(f"window: {programs} programs built, {misses} compiles and "
              f"{hits} cache loads inside the window (expected 0)")
    stages = {k: (stages1[k][0] - marks["stages0"].get(k, (0, 0.0))[0],
                  stages1[k][1] - marks["stages0"].get(k, (0, 0.0))[1])
              for k in stages1}
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    answers = out["answers"]
    ready = ready_times(answers, client.sent)
    if ready:
        taken = sorted(a.done for a in answers if a.result is not None)
        serve.say("window: answers ready at " + ", ".join(
            f"{t - ready[0]:.3f}" for t in ready) + " s, taken at " +
            ", ".join(f"{t - ready[0]:.3f}" for t in taken) + " s")
    errors = [a.result.error for a in answers
              if a.result is not None and a.result.error is not None]
    if errors:
        serve.say(f"window: {len(errors)} error answers, first: {errors[0]}")
    rep = srv.stats.report()
    voxels = [rep[k] - v0 for k, v0 in zip(voxel_keys, marks["voxels0"])]
    compiled = srv.compiled(mix["points"])
    mem_a = compiled.memory_analysis()
    serve.say("program: " + ", ".join(
        f"{k} {getattr(mem_a, k + '_size_in_bytes', None)}"
        for k in ("temp", "argument", "output", "generated_code")))
    names = tracing.hlo_op_names(compiled.as_text()) if trace else {}
    serve.say("server: " + ", ".join(f"{k} {rep[k]}" for k in (
        "bucket_compiles", "cache_loads", "rejected_requests",
        "timed_out_requests", "overflow_requests", "nonfinite_results",
        "mean_batch") + voxel_keys))
    del srv, compiled
    gc.collect()
    t_check = time.perf_counter()
    verdict = check(cfg, mix, pool, params, seed, answers, control)
    serve.say(f"check: {verdict['checked']} answers in "
              f"{time.perf_counter() - t_check!r} s")
    return {"attempted": len(answers),
            "failed": len(errors) + verdict["numbers"]["missing_answers"],
            "setup_s": marks["setup_s"], "memory_peak_bytes": int(mem),
            "numbers": verdict["numbers"], "control": verdict["control"],
            "op_names": names, "stages": stages,
            "rate": period_rate(ready) or out["rate"],
            "rows_per_execution": mix["max_batch"],
            "voxels": {"owned": voxels[0], "computed": voxels[1]}}
