"""The harness end to end on the CPU at a tiny size: it refuses to measure
without a TPU, its check passes the program, and it fails the control (the
reference wholly in bfloat16 in the program's place) and an answer altered
where the server produces it."""
import json
import os
import subprocess
import sys
import time

import numpy as np

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


# wide and deep enough that bfloat16 rounding shows, small enough for a CPU
TINY = {"hidden": 128, "n_mp_layers": 6, "max_points": 512}
LIMITS = load("limits", "xmgn-serve-sweep.json")
LIMIT = LIMITS["fields_rms_err"]


def tiny_run(control: bool = False, seconds: float = 1.0):
    cfg = dict(load("configs", "xmgn-drivaer.json"), **TINY)
    mix = dict(load("traffic", "sweep.json"), points=512, nu=64, nv=32,
               pool_ids=[1, 2, 3], check_sample=2)
    cell = run.load_module("cells", f"{mix['kind']}.py")
    return cell.run(cfg, mix, 2**34 + 11, seconds, False,
                    time.perf_counter(), "", control=control)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join("bench", "run.py"),
                        "--workload", "xmgn-serve-sweep", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_program_passes_and_float8_control_fails():
    # the control is now the reference wholly in bfloat16 (the step below
    # the configuration's float32); the test keeps its first name
    out = tiny_run(control=True)
    correct, checks = run.verdict(out["numbers"], LIMITS)
    assert correct and set(checks) == set(LIMITS)
    assert out["numbers"]["missing_answers"] == 0
    assert out["numbers"]["fields_rms_err"] < LIMIT / 10
    # the control in the program's place fails the same comparison
    correct_c, checks_c = run.verdict(out["control"], LIMITS)
    assert not correct_c
    assert checks_c["fields_rms_err"]["value"] > LIMIT
    assert out["rate"] > 0 and out["setup_s"] > 0
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_altered_answers_fail(monkeypatch):
    from repro.launch import serve_gnn
    harvest = serve_gnn.GNNServer._harvest

    def altered(self, fl):
        results = harvest(self, fl)
        for r in results:
            r.fields = r.fields * np.float32(1.1)
        return results

    monkeypatch.setattr(serve_gnn.GNNServer, "_harvest", altered)
    out = tiny_run()
    correct, checks = run.verdict(out["numbers"], LIMITS)
    assert not correct
    assert checks["fields_rms_err"]["value"] > LIMIT


def test_missing_or_absent_numbers_are_not_correct():
    assert run.verdict({"fields_rms_err": 0.0, "missing_answers": 0},
                       LIMITS)[0]
    assert not run.verdict({"fields_rms_err": None, "missing_answers": 0},
                           LIMITS)[0]
    assert not run.verdict({"fields_rms_err": 0.0}, LIMITS)[0]
    assert not run.verdict({"fields_rms_err": 0.0, "missing_answers": 1},
                           LIMITS)[0]
