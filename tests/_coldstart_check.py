"""Cold-start round trip across REAL process boundaries.

Parent phase: build an autoscaling server with the persistent compile cache
enabled, serve traffic so the ladder adapts, record the outputs, freeze a
deploy artifact, then spawn THIS script again as a child.

Child phase (fresh process, fresh jit caches): restore the server from the
artifact and assert the tentpole claims:
  * the first request is served with ZERO XLA compiles and ZERO host
    recalibrations (shipped grid specs + AOT executables where the backend
    can serialize them; XLA:CPU cannot, so here every program is a
    persistent-cache load — the documented fallback),
  * the adapted ladder and request-size histogram survive the restart,
  * outputs match the parent's bit-for-bit (same deterministic sampling),
  * a NON-artifact server in the same process still compiles nothing: its
    fresh jit trace is satisfied from the persistent compilation cache and
    reported as ``cache_loads``, not ``bucket_compiles``.

Run standalone: PYTHONPATH=src python tests/_coldstart_check.py
"""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from repro.configs.base import GNNConfig
from repro.data import geometry as geo
from repro.launch.serve_gnn import GNNServer

REQS = [(0, 100), (1, 256)]                    # (geometry seed, n_points)


def _cfg(cache_dir):
    return GNNConfig().reduced().replace(
        levels=(64, 128, 256), bucket_granularity=64,
        compile_cache_dir=cache_dir)


def _requests():
    out = []
    for gseed, n in REQS:
        verts, faces = geo.car_surface(geo.sample_params(gseed))
        out.append((verts, faces, n))
    return out


def parent(d):
    cfg = _cfg(os.path.join(d, "xla-cache"))
    srv = GNNServer(cfg, "auto", max_batch=2, seed=3)
    results = srv.serve(_requests())
    rep = srv.stats.report()
    assert rep["bucket_compiles"] == len(srv.ladder()), rep
    art = os.path.join(d, "deploy.msgpack")
    info = srv.save_artifact(art)
    assert set(info["aot_buckets"]) <= set(srv.ladder()), info
    np.save(os.path.join(d, "fields.npy"),
            np.concatenate([r.fields.ravel() for r in results]))
    with open(os.path.join(d, "expect.json"), "w") as f:
        json.dump({"ladder": sorted(srv.target_ladder()),
                   "live": sorted(srv.ladder()),
                   "hist_len": len(srv._size_hist)}, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, os.path.abspath(__file__),
                           "--child", d], capture_output=True, text=True,
                          timeout=900, env=env)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, "child failed"
    assert "CHILD_OK" in proc.stdout
    print("ALL_OK")


def child(d):
    expect = json.load(open(os.path.join(d, "expect.json")))
    want_fields = np.load(os.path.join(d, "fields.npy"))

    srv = GNNServer.from_artifact(os.path.join(d, "deploy.msgpack"))
    assert sorted(srv.ladder()) == expect["live"], srv.ladder()
    assert sorted(srv.target_ladder()) == expect["ladder"]
    assert len(srv._size_hist) == expect["hist_len"]

    results = srv.serve(_requests())
    rep = srv.stats.report()
    # the tentpole: first requests served with zero XLA compiles and zero
    # host recalibration — every program came from the artifact or, where
    # it carries no executable, from the persistent compilation cache
    assert rep["bucket_compiles"] == 0, rep
    assert rep["bucket_calibrations"] == 0, rep
    assert rep["cache_loads"] >= len(expect["live"]), rep
    got = np.concatenate([r.fields.ravel() for r in results])
    np.testing.assert_allclose(got, want_fields, atol=1e-5)

    # stat-split check: a NON-artifact server in this same process traces
    # fresh jit programs, but the backend executables come from the
    # persistent disk cache populated by the parent -> cache_loads, not
    # compiles
    cfg = _cfg(os.path.join(d, "xla-cache"))
    fresh = GNNServer(cfg, tuple(expect["live"]), max_batch=2, seed=3)
    fresh.serve(_requests())
    rep2 = fresh.stats.report()
    assert rep2["bucket_compiles"] == 0, rep2
    assert rep2["cache_loads"] >= len(expect["live"]), rep2
    print("CHILD_OK")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2])
    else:
        with tempfile.TemporaryDirectory() as d:
            parent(d)
