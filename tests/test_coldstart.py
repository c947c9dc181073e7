"""Cold-start elimination: deploy artifacts, calibration caching and the
compile-vs-cache-load stat split.

The cross-process guarantees (a restored server's first request triggers
zero XLA compiles; a fresh server with a warm persistent cache reports
``cache_loads``, not ``bucket_compiles``) run in subprocesses via
``_coldstart_check.py`` — the persistent compilation cache is process-global
JAX config and enabling it here would reclassify the compile counts every
other in-process test asserts on.
"""
import numpy as np
import pytest

from repro.ckpt import artifact as artifact_lib
from repro.ckpt import checkpoint as ckpt
from repro.configs.base import GNNConfig
from repro.data import geometry as geo
from repro.graphx.hashgrid import GridSpec
from repro.graphx.multiscale import MultiscaleSpec
from repro.launch.serve_gnn import GNNServer
from test_distributed import run_script


def _cfg(**kw):
    return GNNConfig().reduced().replace(levels=(64, 128, 256), **kw)


def _geom(i=0):
    return geo.car_surface(geo.sample_params(i))


# --------------------------------------------------- cross-process tentpole

def test_coldstart_roundtrip_subprocess():
    out = run_script("_coldstart_check.py")
    assert "CHILD_OK" in out and "ALL_OK" in out


# ------------------------------------------------------ calibration caching

def test_evict_rebuild_never_recalibrates():
    """An LRU evict→rebuild reuses the cached MultiscaleSpec: the host
    cKDTree calibration runs once per SIZE, not once per build."""
    verts, faces = _geom()
    cfg = _cfg(bucket_granularity=64, max_live_buckets=2)
    srv = GNNServer(cfg, "auto", max_batch=1, seed=9)
    for n in (64, 128, 192, 64):               # last 64 lands post-eviction
        srv.serve([(verts, faces, n)])
    rep = srv.stats.report()
    assert rep["bucket_evictions"] == 2
    assert rep["bucket_misses"] == 4           # 3 builds + the 64 rebuild
    assert rep["bucket_calibrations"] == 3     # but only 3 calibrations
    assert set(srv._calib) == {64, 128, 192}   # specs outlive their buckets


def test_warmup_calibrations_counted_once():
    srv = GNNServer(_cfg(), (64, 128), max_batch=1)
    srv.warmup()
    srv.warmup()
    assert srv.stats.report()["bucket_calibrations"] == 2


# ------------------------------------------------------- artifact structure

def test_multiscale_spec_pack_roundtrip():
    ms = MultiscaleSpec(
        level_sizes=(32, 64), k=6,
        grids=(GridSpec(n_points=32, k=6, resolution=(2, 3, 4),
                        neigh_cap=40, layout="csr"),
               GridSpec(n_points=64, k=6, resolution=(4, 5, 6),
                        neigh_cap=50, layout="csr")))
    assert artifact_lib.unpack_multiscale_spec(
        artifact_lib.pack_multiscale_spec(ms)) == ms


def test_artifact_tree_carries_server_state(tmp_path):
    verts, faces = _geom()
    srv = GNNServer(_cfg(bucket_granularity=64), "auto", max_batch=2, seed=1)
    srv.serve([(verts, faces, 100), (verts, faces, 200)])
    path = str(tmp_path / "deploy.msgpack")
    info = srv.save_artifact(path)
    assert info["buckets"] == sorted(srv.ladder())
    tree = ckpt.restore(path)
    assert tree["format"] == artifact_lib.ARTIFACT_FORMAT
    assert tree["auto"] is True or tree["auto"] == 1
    assert sorted(int(n) for n in tree["live"]) == sorted(srv.ladder())
    assert set(int(k) for k in tree["calib"]) >= set(srv.ladder())
    assert len(tree["size_hist"]) == len(srv._size_hist)
    assert tree["knobs"]["max_batch"] == 2
    assert "verts" in tree["reference"] and "faces" in tree["reference"]


def test_load_artifact_rejects_non_artifact(tmp_path):
    p = str(tmp_path / "ck.msgpack")
    ckpt.save(p, {"params": {}})
    with pytest.raises(ValueError, match="not a deploy artifact"):
        artifact_lib.load_artifact(p)
    with pytest.raises(ValueError, match="not a deploy artifact"):
        GNNServer.from_artifact(p)


def test_shard_spec_roundtrips_through_artifact():
    """Sharded servers save artifacts too: the frozen per-bucket ShardSpec
    (shard topology + merged grids + calibrated halo width) survives the
    msgpack pack/unpack with an identical compiled-program signature.
    (The cross-process sharded save/restore runs in ``_sharded_auto_check``.)
    """
    from repro.graphx import sharded
    from repro.core.graph_build import sample_surface

    verts, faces = _geom(1)
    pts, nrm = sample_surface(verts, faces, 128, np.random.default_rng(0))
    spec = sharded.shard_spec_for(128, 2, 2, 1.3, reference_points=pts,
                                  reference_normals=nrm,
                                  level_sizes=(64, 128), k=4)
    back = artifact_lib.unpack_shard_spec(artifact_lib.pack_shard_spec(spec))
    assert back.signature() == spec.signature()
    assert back.halo_width == spec.halo_width > 0.0


# ----------------------------------------------- in-process restore behavior

def test_from_artifact_matches_source_server(tmp_path, cache_dir):
    verts, faces = _geom(2)
    src = GNNServer(_cfg(compile_cache_dir=cache_dir), (128,), max_batch=2,
                    seed=5)
    [want] = src.serve([(verts, faces, 100)])
    path = str(tmp_path / "deploy.msgpack")
    info = src.save_artifact(path)

    dst = GNNServer.from_artifact(path)
    assert dst.max_batch == 2 and dst.seed == 5
    assert dst.ladder() == (128,)
    [got] = dst.serve([(verts, faces, 100)])
    np.testing.assert_allclose(got.fields, want.fields, atol=1e-5)
    rep = dst.stats.report()
    # in-process restore still compiles nothing: the bucket runs the
    # artifact's AOT executable where the backend could serialize one
    # (not XLA:CPU), else a load from the persistent compilation cache
    assert rep["bucket_compiles"] == 0
    assert rep["bucket_calibrations"] == 0
    assert rep["cache_loads"] >= 1
    assert dst._buckets[128].aot == (128 in info["aot_buckets"])


def test_override_of_baked_knob_drops_aot(tmp_path, monkeypatch):
    # XLA:CPU cannot serialize executables: stand in a payload so the gate
    # that drops AOT programs on a baked-knob override is exercised here
    exe = object()
    monkeypatch.setattr(artifact_lib, "serialize_compiled", lambda c: b"x")
    monkeypatch.setattr(artifact_lib, "deserialize_compiled", lambda b: exe)
    verts, faces = _geom(2)
    src = GNNServer(_cfg(), (128,), max_batch=2, seed=5)
    src.serve([(verts, faces, 100)])
    path = str(tmp_path / "deploy.msgpack")
    info = src.save_artifact(path)
    assert info["aot_buckets"] == [128]
    assert GNNServer.from_artifact(path)._aot == {128: exe}

    dst = GNNServer.from_artifact(path, max_batch=3)   # baked into programs
    assert dst.max_batch == 3
    assert not dst._aot                        # executables dropped
    [res] = dst.serve([(verts, faces, 100)])   # falls back to jit: works
    assert np.isfinite(res.fields).all()
    assert dst.stats.report()["bucket_compiles"] == 1
    # calibration still rides along — specs are shape-independent of
    # max_batch
    assert dst.stats.report()["bucket_calibrations"] == 0
