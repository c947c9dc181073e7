"""Device-resident graph construction (repro.graphx + repro.kernels.knn):
exact parity with the host cKDTree path, Pallas kernel vs XLA reference,
and the single-jit end-to-end inference pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GNNConfig
from repro.core.graph_build import (knn_edges, node_input_features,
                                    sample_surface)
from repro.core.multiscale import (build_multiscale_from_points,
                                   multiscale_edges as host_multiscale)
from repro.data import geometry as geo
from repro.graphx import hashgrid
from repro.graphx import multiscale as dms
from repro.graphx import pipeline as dpipe
from repro.kernels.knn import ops as knn_ops
from repro.kernels.knn import ref as knn_ref
from repro.models import meshgraphnet


def _car_cloud(n, seed=0):
    verts, faces = geo.car_surface(geo.sample_params(seed))
    return sample_surface(verts, faces, n, np.random.default_rng(seed))


def _neighbor_sets(idx, mask):
    return [set(row[m].tolist()) for row, m in zip(np.asarray(idx),
                                                   np.asarray(mask))]


@pytest.mark.parametrize("n,k,seed", [(300, 5, 0), (1024, 6, 1), (97, 3, 2)])
def test_hashgrid_knn_matches_ckdtree(n, k, seed):
    """Calibrated hash-grid kNN returns exactly the cKDTree neighbor sets."""
    from scipy.spatial import cKDTree
    pts, _ = _car_cloud(n, seed)
    spec = hashgrid.calibrate_spec(pts, k)
    assert hashgrid.max_knn_cell_ratio(pts, n, spec) <= 1.0
    assert hashgrid.overflow_count(pts, n, spec) == 0
    idx, d2, mask = jax.jit(hashgrid.knn, static_argnames=("spec",))(
        jnp.asarray(pts), n, spec)
    _, tidx = cKDTree(pts).query(pts, k=k + 1)
    got = _neighbor_sets(idx, mask)
    for i in range(n):
        assert got[i] == set(tidx[i][1:].tolist()), i


def test_hashgrid_knn_random_cloud_padding():
    """Random gaussian cloud + padded buffer: padding is never a neighbor."""
    from scipy.spatial import cKDTree
    rng = np.random.default_rng(3)
    n, n_pad, k = 400, 512, 5
    pts = rng.normal(size=(n, 3)).astype(np.float32)
    buf = np.full((n_pad, 3), 77.0, np.float32)   # far-away garbage padding
    buf[:n] = pts
    spec = hashgrid.calibrate_spec(pts, k, n_points=n_pad)
    idx, _, mask = hashgrid.knn(jnp.asarray(buf), n, spec)
    idx, mask = np.asarray(idx), np.asarray(mask)
    assert not mask[n:].any()
    assert (idx[mask] < n).all()
    _, tidx = cKDTree(pts).query(pts, k=k + 1)
    got = _neighbor_sets(idx[:n], mask[:n])
    assert all(got[i] == set(tidx[i][1:].tolist()) for i in range(n))


def test_knn_pallas_kernel_matches_ref():
    rng = np.random.default_rng(4)
    n, c, k = 200, 70, 6
    q = rng.normal(size=(n, 3)).astype(np.float32)
    ci = rng.integers(0, n, size=(n, c)).astype(np.int32)
    cv = rng.random((n, c)) < 0.75
    cp = q[ci]
    args = (jnp.asarray(q), jnp.asarray(cp), jnp.asarray(ci), jnp.asarray(cv))
    i_ref, d_ref, m_ref = knn_ref.topk_neighbors(*args, k)
    i_pl, d_pl, m_pl = knn_ops.topk_neighbors(*args, k, impl="pallas")
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_pl))
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_pl),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(m_ref), np.asarray(m_pl))


def test_hashgrid_pallas_impl_matches_xla():
    pts, _ = _car_cloud(384, 5)
    spec = hashgrid.calibrate_spec(pts, 6)
    ix, _, mx = hashgrid.knn(jnp.asarray(pts), 384, spec, impl="xla")
    ip, _, mp = hashgrid.knn(jnp.asarray(pts), 384, spec, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ix), np.asarray(ip))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(mp))


def test_symmetric_edges_match_host_knn_edges():
    """Device symmetric closure == host knn_edges(bidirectional=True)."""
    pts, _ = _car_cloud(512, 6)
    k = 4
    spec = hashgrid.calibrate_spec(pts, k)
    idx, _, mask = hashgrid.knn(jnp.asarray(pts), 512, spec)
    s, r, em = map(np.asarray, hashgrid.symmetric_edges(idx, mask))
    dev_pairs = list(zip(s[em].tolist(), r[em].tolist()))
    hs, hr = knn_edges(pts, k)
    assert len(dev_pairs) == len(set(dev_pairs)), "duplicate edges emitted"
    assert set(dev_pairs) == set(zip(hs.tolist(), hr.tolist()))
    # masked slots are parked at (0, 0)
    assert (s[~em] == 0).all() and (r[~em] == 0).all()


def test_multiscale_edges_match_host_union():
    levels = (128, 256, 512)
    k = 4
    pts, _ = _car_cloud(levels[-1], 7)
    grids = tuple(hashgrid.calibrate_spec(pts[:n], k, n_points=n)
                  for n in levels)
    ms = dms.MultiscaleSpec(level_sizes=levels, k=k, grids=grids)
    s, r, em = jax.jit(dms.multiscale_edges, static_argnames=("ms",))(
        jnp.asarray(pts), levels[-1], ms)
    s, r, em = map(np.asarray, (s, r, em))
    hs, hr, hl = host_multiscale(pts, levels, k)
    dev_pairs = list(zip(s[em].tolist(), r[em].tolist()))
    assert len(dev_pairs) == len(set(dev_pairs))
    assert set(dev_pairs) == set(zip(hs.tolist(), hr.tolist()))
    # per-level tags agree (both keep the coarsest occurrence)
    lvl = ms.level_of_edge
    for l in range(len(levels)):
        dev_l = set(zip(s[em & (lvl == l)].tolist(),
                        r[em & (lvl == l)].tolist()))
        host_l = set(zip(hs[hl == l].tolist(), hr[hl == l].tolist()))
        assert dev_l == host_l, f"level {l}"


def test_end_to_end_jitted_pipeline_matches_host():
    """One jit: padded cloud -> prediction; parity with the host pipeline
    (cKDTree graph + numpy features + model) within 1e-4."""
    cfg = GNNConfig().reduced().replace(levels=(128, 256, 512))
    n = max(cfg.levels)
    pts, normals = _car_cloud(n, 8)
    params = meshgraphnet.init(jax.random.PRNGKey(0), cfg)

    g = build_multiscale_from_points(pts, cfg.levels, cfg.k_neighbors,
                                     normals=normals)
    feats = node_input_features(pts, normals, cfg.fourier_freqs)
    pred_host = meshgraphnet.apply(
        params, cfg, jnp.asarray(feats), jnp.asarray(g.edge_feats),
        jnp.asarray(g.senders), jnp.asarray(g.receivers))

    grids = tuple(hashgrid.calibrate_spec(pts[:m], cfg.k_neighbors,
                                          n_points=m) for m in cfg.levels)
    ms = dms.MultiscaleSpec(level_sizes=cfg.levels, k=cfg.k_neighbors,
                            grids=grids)
    infer = dpipe.make_infer_fn(cfg, ms)
    pred_dev = infer(params, jnp.asarray(pts), jnp.asarray(normals), n)
    np.testing.assert_allclose(np.asarray(pred_dev), np.asarray(pred_host),
                               atol=1e-4)


def test_pipeline_normalization_roundtrip():
    """norm_in/norm_out constants are folded into the compiled program."""
    cfg = GNNConfig().reduced().replace(levels=(64, 128))
    n = max(cfg.levels)
    pts, normals = _car_cloud(n, 9)
    params = meshgraphnet.init(jax.random.PRNGKey(1), cfg)
    grids = tuple(hashgrid.calibrate_spec(pts[:m], cfg.k_neighbors,
                                          n_points=m) for m in cfg.levels)
    ms = dms.MultiscaleSpec(level_sizes=cfg.levels, k=cfg.k_neighbors,
                            grids=grids)
    mu_in = np.zeros((1, cfg.node_in), np.float32)
    sd_in = np.ones((1, cfg.node_in), np.float32)
    mu_out = np.full((1, cfg.node_out), 2.0, np.float32)
    sd_out = np.full((1, cfg.node_out), 3.0, np.float32)
    plain = dpipe.make_infer_fn(cfg, ms)
    normed = dpipe.make_infer_fn(cfg, ms, norm_in=(mu_in, sd_in),
                                 norm_out=(mu_out, sd_out))
    p0 = np.asarray(plain(params, jnp.asarray(pts), jnp.asarray(normals), n))
    p1 = np.asarray(normed(params, jnp.asarray(pts), jnp.asarray(normals), n))
    np.testing.assert_allclose(p1, p0 * 3.0 + 2.0, rtol=1e-5, atol=1e-5)


def test_batched_infer_consistency():
    """vmapped bucket fn == per-request fn for a mixed batch."""
    cfg = GNNConfig().reduced().replace(levels=(64, 128))
    n = max(cfg.levels)
    params = meshgraphnet.init(jax.random.PRNGKey(2), cfg)
    clouds = [_car_cloud(n, s) for s in (10, 11, 12)]
    ref_pts = clouds[0][0]
    grids = tuple(hashgrid.calibrate_spec(ref_pts[:m], cfg.k_neighbors,
                                          n_points=m) for m in cfg.levels)
    ms = dms.MultiscaleSpec(level_sizes=cfg.levels, k=cfg.k_neighbors,
                            grids=grids)
    single = dpipe.make_infer_fn(cfg, ms)
    batched = dpipe.make_batched_infer_fn(cfg, ms)
    bp = jnp.stack([jnp.asarray(p) for p, _ in clouds])
    bn = jnp.stack([jnp.asarray(m) for _, m in clouds])
    out = batched(params, bp, bn, jnp.full((3,), n, jnp.int32))
    for i, (p, m) in enumerate(clouds):
        ref = single(params, jnp.asarray(p), jnp.asarray(m), n)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   atol=1e-5)
