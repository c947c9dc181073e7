"""X-UNet3D volume serving on the CPU at reduced size: the device features
equal the cKDTree definition at every voxel, the in-program slab scan
equals the full-domain forward, a ``GNNServer`` volume bucket answers what
a host reference computes, and the modes the family lacks refuse it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.spatial import cKDTree

from repro.configs.base import UNetConfig
from repro.core import unet_halo
from repro.core.graph_build import sample_surface
from repro.data import geometry as geo
from repro.graphx import volume
from repro.launch.serve_gnn import GNNServer
from repro.models import xunet3d

CFG = UNetConfig().reduced()          # depth 2, base 8, grid (32, 16, 16)


def reference_features(spec, pts, nrm):
    """The features by their definition, with cKDTree in float64:
    (X, Y, Z, 16) and each voxel's distance to its band-nearest sample
    (inf where none lies within the band)."""
    xs, ys, zs = volume.voxel_axes(spec)
    v = np.stack(np.meshgrid(xs, ys, zs, indexing="ij"), -1)
    v = v.reshape(-1, 3).astype(np.float64)
    p = np.asarray(pts, np.float64)
    # cKDTree's bound is strict: the band holds |v - p| <= c
    c = np.nextafter(spec.band * spec.voxel, np.inf)
    d, q = cKDTree(p).query(v, distance_upper_bound=c)
    far = ~np.isfinite(d)
    q[far] = cKDTree(p[:spec.prefix]).query(v[far])[1]
    dv = v - p[q]
    dist = np.linalg.norm(dv, axis=1)
    s = np.sign(np.sum(dv * np.asarray(nrm, np.float64)[q], axis=1))
    grad = dv * np.where(dist > 0, s / np.where(dist > 0, dist, 1), 0)[:, None]
    f = np.concatenate([v, np.sin(np.pi * v), np.cos(np.pi * v),
                        np.sin(2 * np.pi * v), (s * dist)[:, None], grad], 1)
    return f.reshape(*spec.grid, 16), d.reshape(spec.grid)


def device_features(spec, pts, nrm):
    def f(p, n):
        sdf = volume.sdf_grid(p, n, p.shape[0], spec)
        return volume.slab_features(sdf, 0, spec.grid[0], spec)[0]
    return np.asarray(jax.jit(f)(jnp.asarray(pts), jnp.asarray(nrm)))


def car_cloud(n, seed=0):
    v, f = geo.car_surface(geo.sample_params(seed))
    return sample_surface(v, f, n, np.random.default_rng(seed))


@pytest.mark.parametrize("cell_chunk", [30, 5])   # one chunk; four, uneven
def test_features_match_ckdtree_including_band_edge_and_prefix(
        cell_chunk, monkeypatch):
    monkeypatch.setattr(volume, "CELL_CHUNK", cell_chunk)
    pts, nrm = car_cloud(512)
    spec = volume.calibrate_spec(pts, CFG, 512)
    xs, ys, zs = volume.voxel_axes(spec)
    c = spec.band * spec.voxel
    # samples exactly on the band's edge of a voxel and just beyond it
    extra = np.array([[xs[-2] + c, ys[1], zs[1]],
                      [xs[1], ys[-2], zs[-2] + c * 1.01]], np.float32)
    pts = np.concatenate([pts, extra])
    nrm = np.concatenate([nrm, [[1.0, 0.0, 0.0], [0.0, 0.0, 1.0]]]
                         ).astype(np.float32)
    spec = volume.calibrate_spec(pts, CFG, len(pts))
    want, band_d = reference_features(spec, pts, nrm)
    got = device_features(spec, pts, nrm)
    # the band's edge decides which sample a voxel takes: exact float64
    # and float32 may differ only for voxels within rounding of it
    edge = np.abs(band_d - c) < 1e-5
    assert edge[-2, 1, 1]                      # a voxel exactly on the edge
    np.testing.assert_allclose(np.abs(got[..., 12]), np.abs(want[..., 12]),
                               rtol=1e-5, atol=1e-6)
    keep = ~edge
    np.testing.assert_allclose(got[keep], want[keep], rtol=1e-5, atol=2e-5)
    assert abs(got[-2, 1, 1, 12]) == pytest.approx(c)
    assert abs(got[1, -2, -2, 12]) > c
    # voxels beyond the band took the nearest of the prefix, which is
    # farther than the whole cloud's nearest for some of them
    far = np.isinf(band_d)
    assert far.any() and (~far).any()
    v = np.stack(np.meshgrid(xs, ys, zs, indexing="ij"), -1)[far]
    full_d = cKDTree(pts.astype(np.float64)).query(v.astype(np.float64))[0]
    sdf = np.abs(got[..., 12][far])
    assert np.all(sdf >= full_d - 1e-5) and np.any(sdf > full_d + 1e-2)


def slab_model(x_len=40, n_parts=3):
    cfg = CFG.replace(grid=(x_len, 8, 8), halo=10, n_partitions=n_parts)
    params = xunet3d.init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, *cfg.grid, 16)).astype(np.float32))
    return cfg, params, x


def test_slab_scan_equals_full_forward_with_clamped_and_uneven_slabs():
    cfg, params, x = slab_model()
    sw = unet_halo.slab_windows(cfg.grid[0], cfg.n_partitions, cfg.halo, 2)
    # the first window is clamped at 0, the last slab is shifted back and
    # re-owns planes of its neighbour, the last window ends at the domain
    assert sw.owned_starts[0] == sw.window_starts[0] == 0
    assert sw.window_starts[1] > 0
    assert sw.owned_starts[-1] + sw.owned == cfg.grid[0]
    assert sw.owned_starts[-1] < sw.owned_starts[-2] + sw.owned
    assert sw.window_starts[-1] + sw.window == cfg.grid[0]
    full = np.moveaxis(np.asarray(xunet3d.apply(params, cfg, x))[0], -1, 0)

    def slab(x0):
        xw = jax.lax.dynamic_slice_in_dim(x, x0, sw.window, axis=1)
        return jnp.moveaxis(xunet3d.apply(params, cfg, xw)[0], -1, 0)

    out = jax.jit(lambda: unet_halo.scan_slabs(
        slab, sw, (cfg.out_channels,) + cfg.grid, jnp.float32))()
    np.testing.assert_allclose(np.asarray(out), full, rtol=1e-5, atol=1e-5)


def test_slab_scan_with_a_short_halo_differs():
    cfg, params, x = slab_model()
    sw = unet_halo.slab_windows(cfg.grid[0], cfg.n_partitions, 2, 2)
    full = np.moveaxis(np.asarray(xunet3d.apply(params, cfg, x))[0], -1, 0)

    def slab(x0):
        xw = jax.lax.dynamic_slice_in_dim(x, x0, sw.window, axis=1)
        return jnp.moveaxis(xunet3d.apply(params, cfg, xw)[0], -1, 0)

    out = unet_halo.scan_slabs(slab, sw, (4,) + cfg.grid, jnp.float32)
    assert float(np.abs(np.asarray(out) - full).max()) > 1e-4


def reference_fields(cfg, params, spec, pts, nrm):
    feats, _ = reference_features(spec, pts, nrm)
    x = jnp.asarray(feats[None].astype(np.float32))
    with jax.default_matmul_precision("highest"):
        return np.asarray(xunet3d.apply(params, cfg, x))[0]


@pytest.fixture(scope="module")
def volume_server():
    cfg = CFG.replace(n_partitions=3)
    srv = GNNServer(cfg, (512,), max_batch=1, seed=3)
    yield srv
    srv.stop()


def test_volume_bucket_matches_reference(volume_server):
    srv = volume_server
    verts, faces = geo.car_surface(geo.sample_params(2))
    rid = srv.submit(verts, faces, 512)
    (res,) = srv.flush()
    assert res.request_id == rid and res.error is None
    assert res.fields.shape == (*srv.cfg.grid, 4)
    _, nrm = sample_surface(verts, faces, 512,
                            np.random.default_rng((3, rid + 1)))
    want = reference_fields(srv.cfg, srv.params, srv._buckets[512].ms,
                            res.points, nrm)
    np.testing.assert_allclose(res.fields, want, rtol=1e-4, atol=1e-5)
    rep = srv.stats.report()
    sw = unet_halo.slab_windows(32, 3, srv.cfg.halo, 2)
    assert rep["volume_voxels_owned"] == 32 * 16 * 16
    assert rep["volume_voxels_computed"] == sw.n * sw.window * 16 * 16
    assert "serve_volume_voxels_owned_total" in \
        srv.stats.metrics.prometheus_text()


def test_volume_band_overflow_is_rejected_and_counted(volume_server):
    srv = volume_server
    b = srv._buckets[512]
    full = b.ms
    b.ms = volume.spec_for(srv.cfg, 512, 1)
    try:
        verts, faces = geo.car_surface(geo.sample_params(2))
        before = srv.stats.overflow_requests
        srv.submit(verts, faces, 512)
        with pytest.warns(UserWarning, match="overflows"):
            (res,) = srv.flush()
    finally:
        b.ms = full
    assert res.error is not None and "overflow" in res.error
    assert srv.stats.overflow_requests == before + 1


def test_sharded_rollout_and_artifact_modes_refuse_the_unet_family(
        volume_server, tmp_path):
    with pytest.raises(ValueError, match="sharded"):
        GNNServer(CFG, (512,), shard_devices=2)
    with pytest.raises(ValueError, match="rollout"):
        volume_server.rollout_engine()
    with pytest.raises(ValueError, match="artifact"):
        volume_server.save_artifact(str(tmp_path / "a.msgpack"))
