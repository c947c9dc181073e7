"""A serving cell: ``GNNServer`` driven by ``start``/``submit``/``result``.

Set-up makes the weights from the seed (on the device, one jitted call),
builds the geometry pool, constructs the server with the cell's one bucket,
compiles it (``warmup``), starts the background worker and sends a warm
burst of the cell's own traffic. The window then keeps ``outstanding``
geometries in flight (a design sweep): it starts and ends at a completion,
and ``rate`` is the completions after its start over its length. The
profiler is off unless the run is traced.

After the window the memory peak is read, the server stopped, and a sample
of the window's answers, drawn from the seed, is compared with the plain
reference (``bench/reference.py``), starting from the cloud each answer
carries. The numbers compared are ``fields_rms_err`` (the largest root mean
square gap of an answer's fields from the reference's) and
``missing_answers``; ``bench/run.py`` holds them against the cell's limits.
"""
from __future__ import annotations

import gc
import math
import sys
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic, tracing

RESULT_WAIT_S = 60.0           # how long past the window an answer may come


def say(msg: str):
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Answer:
    rid: int
    pool: int
    done: float = math.inf     # when ``result`` returned it
    result: object = None      # repro Result, or None if it never came


def gnn_config(cfg: dict, points: int, telemetry: bool):
    """The program's config object for a benchmark config."""
    from repro.configs.base import GNNConfig
    return GNNConfig(
        name=cfg["name"], hidden=cfg["hidden"],
        n_mp_layers=cfg["n_mp_layers"], mlp_layers=cfg["mlp_layers"],
        act=cfg["act"], k_neighbors=cfg["k_neighbors"],
        node_in=cfg["node_in"], edge_in=cfg["edge_in"],
        node_out=cfg["node_out"],
        fourier_freqs=tuple(cfg["fourier_freqs"]),
        levels=reference.level_sizes(points, cfg["n_levels"]),
        telemetry=telemetry)


def stage_totals(srv) -> Dict[str, tuple]:
    rep = srv.stats.report()
    out = {s: (v["count"], v["total_s"]) for s, v in rep["stages"].items()}
    out["requests"] = (rep["requests"], 0.0)
    out["programs"] = (rep["bucket_compiles"] + rep["cache_loads"], 0.0)
    return out


def closed_loop(srv, pool, mix, seed, seconds, cap: tracing.Capture,
                on_start) -> dict:
    """Keep ``outstanding`` geometries in flight; see the module docstring."""
    order = traffic.closed_order(mix, seed)
    n_points = mix["points"]
    inflight: deque = deque()

    def send():
        i = next(order)
        with jax.profiler.TraceAnnotation("bench/submit"):
            rid = srv.submit(*pool[i], n_points)
        inflight.append(Answer(rid, i))

    def take(limit_s: float) -> Answer:
        a = inflight.popleft()
        with jax.profiler.TraceAnnotation("bench/wait_result"):
            try:
                a.result = srv.result(a.rid, timeout=limit_s)
                a.done = time.perf_counter()
            except TimeoutError:
                pass
        return a

    for _ in range(mix["outstanding"]):
        send()
    for _ in range(mix["warm_requests"]):
        take(RESULT_WAIT_S + seconds)
        send()
    on_start()
    t0 = time.perf_counter()
    cap.start()
    window: List[Answer] = []
    t_end = t0
    while True:
        a = take(RESULT_WAIT_S + seconds)
        window.append(a)
        if a.result is None:
            t_end = time.perf_counter()
            break
        t_end = a.done
        cap.tick(t_end)
        if t_end - t0 >= seconds:
            break
        send()
    cap.stop()
    while inflight:
        take(RESULT_WAIT_S)
    done = sum(1 for a in window if a.result is not None)
    return {"answers": window, "rate": done / (t_end - t0)}


def control_fields(cfg, params, pts, nrm, s, r) -> np.ndarray:
    """The control in the program's place: the reference computed wholly in
    bfloat16 (the step below the configuration's float32), featurization
    from the bfloat16-rounded cloud included."""
    nf, ef = reference.low_precision_inputs(cfg, pts, nrm, s, r,
                                            jnp.bfloat16)
    return reference.forward(params, cfg, nf, ef, s, r, jnp.bfloat16)


def check(cfg: dict, mix: dict, pool, params, seed: int,
          answers: List[Answer], control: bool = False) -> dict:
    """Compare a seeded sample of the window's answers with the reference.

    Returns ``{numbers, control, checked, edges, far_normals}``:
    ``numbers`` are the program's numbers compared (``fields_rms_err``,
    ``missing_answers``); with ``control`` set, ``control`` holds the same
    numbers with the control's fields in place of the served ones, on the
    same clouds. An error answer is a failure, not a wrong answer, and is
    not compared."""
    missing = sum(1 for a in answers if a.result is None)
    ok = [a for a in answers
          if a.result is not None and a.result.error is None]
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(len(ok), size=min(mix["check_sample"], len(ok)),
                      replace=False) if ok else []
    worst, worst_control = 0.0, 0.0
    edges, far = [], 0
    for j in sorted(int(p) for p in pick):
        a = ok[j]
        verts, faces = pool[a.pool]
        pts = np.asarray(a.result.points, np.float32)
        nrm, n_far = reference.point_normals(verts, faces, pts)
        far += n_far
        nf, ef, s, r = reference.graph_inputs(cfg, pts, nrm)
        edges.append(len(s))
        want = reference.forward(params, cfg, nf, ef, s, r)
        gap = reference.rms_gap(a.result.fields, want)
        say(f"check: request {a.rid} (pool {a.pool}, {len(s)} edges, "
            f"fields rms {reference.rms_gap(want, 0.0)!r}): "
            f"fields_rms_err {gap!r}")
        worst = max(worst, gap)
        if control:
            low = control_fields(cfg, params, pts, nrm, s, r)
            gap_c = reference.rms_gap(low, want)
            say(f"control: request {a.rid}: fields_rms_err {gap_c!r}")
            worst_control = max(worst_control, gap_c)
    # no answer compared is no evidence: no number, and the run is not correct
    numbers = {"fields_rms_err": worst if len(pick) else None,
               "missing_answers": missing}
    return {"numbers": numbers,
            "control": (dict(numbers, fields_rms_err=worst_control)
                        if control and len(pick) else None),
            "checked": len(pick), "edges": edges, "far_normals": far}


def start_server(cfg: dict, mix: dict, seed: int, telemetry: bool):
    """Weights, geometry pool, and a started server with its one bucket
    compiled (or loaded from the compile cache)."""
    from repro.launch.serve_gnn import GNNServer

    points = mix["points"]
    if points > cfg["max_points"]:
        raise ValueError(f"traffic sends {points} points; config "
                         f"{cfg['name']} is cut at {cfg['max_points']}")
    pool = traffic.geometry_pool(mix)
    params = reference.init_params(cfg, seed)
    srv = GNNServer(gnn_config(cfg, points, telemetry), (points,),
                    params=params, max_batch=mix["max_batch"],
                    n_levels=cfg["n_levels"], seed=int(seed),
                    async_flush=True)
    srv.warmup()
    srv.start(deadline_s=mix["deadline_ms"] / 1e3)
    return srv, pool, params


def op_names(srv, n: int) -> Dict[str, str]:
    """Instruction -> named-scope path of the bucket program, for trace
    events that do not carry the scope themselves. The server has no public
    handle on its compiled program; this lowers its bucket function again
    (a compile-cache hit) through its private ``_buckets`` and
    ``_bucket_arg_specs``."""
    text = srv._buckets[n].infer.lower(
        *srv._bucket_arg_specs(n)).compile().as_text()
    return tracing.hlo_op_names(text)


def run(cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
        t_process: float, trace_dir: str, control: bool = False) -> dict:
    """Set up, measure, check: what ``bench/run.py`` and the metric readers
    need (see there)."""
    from repro.ckpt import compile_cache

    srv, pool, params = start_server(cfg, mix, seed, trace)
    cap = tracing.Capture(trace, trace_dir, mix["trace_s"])
    marks: dict = {}

    def on_start():
        marks["setup_s"] = time.perf_counter() - t_process
        marks["stages0"] = stage_totals(srv)
        marks["events0"] = compile_cache.CompileEvents()

    try:
        out = closed_loop(srv, pool, mix, seed, seconds, cap, on_start)
    finally:
        srv.stop()
    stages1 = stage_totals(srv)
    misses, hits = marks["events0"].delta()
    programs = stages1["programs"][0] - marks["stages0"]["programs"][0]
    say(f"window: {programs} programs built, {misses} compiles and {hits} "
        "cache loads inside the window (expected 0)")
    stages = {k: (stages1[k][0] - marks["stages0"].get(k, (0, 0.0))[0],
                  stages1[k][1] - marks["stages0"].get(k, (0, 0.0))[1])
              for k in stages1}
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    answers = out["answers"]
    errors = [a.result.error for a in answers
              if a.result is not None and a.result.error is not None]
    if errors:
        say(f"window: {len(errors)} error answers, first: {errors[0]}")
    names = op_names(srv, mix["points"]) if trace else {}
    rep = srv.stats.report()
    say("server: " + ", ".join(f"{k} {rep[k]}" for k in (
        "bucket_compiles", "cache_loads", "rejected_requests",
        "timed_out_requests", "overflow_requests", "nonfinite_results",
        "mean_batch")))
    del srv
    gc.collect()
    t_check = time.perf_counter()
    verdict = check(cfg, mix, pool, params, seed, answers, control)
    say(f"check: {verdict['checked']} answers in "
        f"{time.perf_counter() - t_check!r} s; {verdict['far_normals']} "
        "points needed a wide search for their triangle")
    edges = verdict["edges"]
    return {"attempted": len(answers),
            "failed": len(errors) + verdict["numbers"]["missing_answers"],
            "setup_s": marks["setup_s"], "memory_peak_bytes": int(mem),
            "numbers": verdict["numbers"], "control": verdict["control"],
            "op_names": names, "rate": out["rate"], "stages": stages,
            "rows_per_execution": mix["max_batch"],
            "graph": {"nodes": mix["points"],
                      "edges": float(np.mean(edges)) if edges else None}}
