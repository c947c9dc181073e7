"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/readings.py --workload <cell> --seeds 7,8,9 --seconds 4

For each seed, in this one process: a short window of the cell's own
traffic at its own size (the cell module's ``run`` with ``control`` set),
then the seeded sample of its answers compared with the reference (the
program's numbers) and the control put in the program's place on the same
inputs (the control's numbers). Both go through ``run.verdict`` with the
cell's limits, as a benchmark run's would. One line per seed and a summary
per number: the lower reading is the largest the program gives, the upper
the smallest the control gives. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative whole numbers")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.find(spec["workloads"], args.workload, "workload")
    run.device_info(cell["chips"])
    from repro.ckpt import compile_cache
    compile_cache.enable()
    cfg, mix, mod, limits = run.cell_inputs(cell)
    program, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = mod.run(cfg, mix, seed, args.seconds, False,
                      time.perf_counter(), run.TRACE_DIR, control=True)
        ok_p, _ = run.verdict(out["numbers"], limits)
        ok_c, _ = run.verdict(out["control"] or {}, limits)
        program.append(out["numbers"])
        control.append(out["control"] or {})
        print(json.dumps({"seed": seed, "program": out["numbers"],
                          "program_correct": ok_p,
                          "control": out["control"],
                          "control_correct": ok_c,
                          "setup_s": out["setup_s"]}), flush=True)
    for k in limits:
        lo = [p.get(k) for p in program]
        up = [c.get(k) for c in control]
        lower = max((x for x in lo if x is not None), default=None)
        upper = min((x for x in up if x is not None), default=None)
        print(json.dumps({"workload": cell["name"], "number": k,
                          "seeds": len(program), "lower": lower,
                          "upper": upper, "limit": limits[k]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
