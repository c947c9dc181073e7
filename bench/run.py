"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data found by name: the cell in
``BENCHMARK.json`` names a config (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); the mix's ``kind`` names the
module that sets up, measures and checks it (``bench/cells/<kind>.py``);
the limits of the comparison that decides ``correct`` are
``bench/limits/<cell>.json``, one per number the cell compares; each metric
is read by ``bench/metrics/<metric>.py``.

A cell module has ``run(cfg, mix, seed, seconds, trace, t_process,
trace_dir)``, returning a dict with at least ``attempted``, ``failed``,
``setup_s``, ``memory_peak_bytes``, ``numbers`` (each number compared, by
the name its limit has; None where it could not be read) and, when traced,
``op_names`` (HLO instruction -> named-scope path) and
``rows_per_execution``. Metric readers get it as ``ctx["run"]``, beside
``config``, ``traffic``, ``peaks`` and the reduced ``trace``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: each number compared, beside its limit. Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Refused(RuntimeError):
    """The run cannot be made here; no result is printed."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    """A module of the benchmark found by its file's path under bench/."""
    path = os.path.join(BENCH, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, trace: bool):
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def verdict(numbers: dict, limits: dict):
    """(correct, checks): every number the limits name, beside its limit; a
    number that is missing or above its limit makes the run incorrect."""
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in limits.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks


def device_info(chips: int) -> dict:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found "
                      f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


def cell_inputs(cell: dict):
    """(config, traffic mix, cell module, limits) of a cell."""
    from bench import traffic
    cfg = load_json(BENCH, "configs", f"{cell['config']}.json")
    mix = traffic.load_mix(cell["traffic"])
    mod = load_module("cells", f"{mix['kind']}.py")
    limits = load_json(BENCH, "limits", f"{cell['name']}.json")
    return cfg, mix, mod, limits


def reduce_trace(run: dict) -> dict:
    """The traced window reduced (``bench/tracing.py``). A trace with no
    whole execution of the cell's program, or with no op under any named
    scope, cannot feed the metrics: the run fails."""
    from bench import tracing
    reduced = tracing.reduce_trace(tracing.load(TRACE_DIR), run["op_names"])
    if not reduced["executions"]:
        raise RuntimeError("trace: no whole program execution inside the "
                           "traced span")
    if not reduced["path_s"]:
        raise RuntimeError("trace: no device op carries a named scope")
    return reduced


def measure(spec: dict, cell: dict, seed: int, seconds: float, trace: bool,
            device: dict) -> dict:
    """Set up, measure and check one cell on ``device``; the result line."""
    peaks = load_json(BENCH, "peaks.json")
    if device["kind"] not in peaks:
        raise Refused(f"no peaks for device kind {device['kind']!r} in "
                      "bench/peaks.json")
    cfg, mix, mod, limits = cell_inputs(cell)
    run = mod.run(cfg, mix, seed, seconds, trace, T_PROCESS, TRACE_DIR)
    reduced = reduce_trace(run) if trace else None
    ctx = {"run": run, "config": cfg, "traffic": mix,
           "peaks": peaks[device["kind"]], "trace": reduced}
    metrics = {}
    for m in cell_metrics(spec, cell["name"], trace):
        value = load_module("metrics", f"{m['name']}.py").read(ctx)
        if value is None:
            print(f"metric {m['name']}: nothing to read in this run",
                  file=sys.stderr, flush=True)
        else:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, checks = verdict(run["numbers"], limits)
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("run: --seed must be a non-negative whole number",
              file=sys.stderr)
        return 2
    try:
        spec = load_json(ROOT, "BENCHMARK.json")
        cell = find(spec["workloads"], args.workload, "workload")
        # the program keeps its compile cache where this variable says
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
        device = device_info(cell["chips"])
        from repro.ckpt import compile_cache
        compile_cache.enable()
        line = measure(spec, cell, args.seed, args.seconds,
                       bool(args.trace), device)
    except (Refused, FileNotFoundError) as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
