"""Occupied-cell CSR hash grid: hypothesis-driven exactness vs cKDTree over
adversarial cloud families, dense-vs-CSR regression, and the O(points)
memory property (resolutions whose dense table could never be allocated)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph_build import sample_surface
from repro.data import geometry as geo
from repro.graphx import hashgrid


def _make_cloud(family: str, n: int, rng: np.random.Generator) -> np.ndarray:
    if family == "uniform":
        return rng.random((n, 3)).astype(np.float32)
    if family == "clustered":
        k = max(n // 32, 1)
        centers = rng.random((k, 3)).astype(np.float32) * 10.0
        return (centers[rng.integers(0, k, n)]
                + rng.normal(scale=0.05, size=(n, 3))).astype(np.float32)
    if family == "coplanar":
        pts = rng.random((n, 3)).astype(np.float32)
        pts[:, 2] = 0.25          # degenerate axis: zero extent
        return pts
    if family == "duplicates":
        base = rng.random((max(n // 3, 1), 3)).astype(np.float32)
        return base[rng.integers(0, len(base), n)]
    raise ValueError(family)


def _assert_knn_matches_ckdtree(pts: np.ndarray, k: int,
                                spec: hashgrid.GridSpec):
    """Compare against cKDTree robustly under distance ties (duplicate or
    symmetric points): the sorted neighbor distances must agree exactly, and
    where the k-th distance is unique the neighbor *sets* must agree."""
    from scipy.spatial import cKDTree
    n = len(pts)
    idx, d2, mask = jax.jit(hashgrid.knn, static_argnames=("spec",))(
        jnp.asarray(pts), n, spec)
    idx, d2, mask = map(np.asarray, (idx, d2, mask))
    kq = min(k + 2, n)   # one spare row to detect k-th-distance ties
    tdist, tidx = cKDTree(pts).query(pts, k=kq)
    tdist, tidx = np.atleast_2d(tdist), np.atleast_2d(tidx)
    for i in range(n):
        pairs = [(d, j) for d, j in zip(tdist[i], tidx[i]) if j != i]
        true_nbrs = pairs[:k]
        got = sorted(zip(np.sqrt(d2[i][mask[i]]), idx[i][mask[i]]))
        assert len(got) == len(true_nbrs), i
        np.testing.assert_allclose([d for d, _ in got],
                                   [d for d, _ in true_nbrs],
                                   rtol=1e-4, atol=1e-6, err_msg=f"query {i}")
        unique_kth = (len(pairs) <= k
                      or pairs[k][0] > true_nbrs[-1][0] + 1e-6)
        if unique_kth:
            # no tie at the k-th boundary: neighbor sets must match exactly
            assert {j for _, j in got} == {j for _, j in true_nbrs}, i


@settings(max_examples=12, deadline=None)
@given(
    family=st.sampled_from(["uniform", "clustered", "coplanar", "duplicates"]),
    n=st.integers(30, 400),
    k=st.integers(2, 8),
    seed=st.integers(0, 10_000),
)
def test_csr_knn_exact_property(family, n, k, seed):
    rng = np.random.default_rng(seed)
    pts = _make_cloud(family, n, rng)
    spec = hashgrid.calibrate_spec(pts, k, layout="csr")
    assert spec.layout == "csr"
    assert hashgrid.overflow_count(pts, n, spec) == 0
    _assert_knn_matches_ckdtree(pts, k, spec)


@pytest.mark.parametrize("family,n,k,seed", [
    ("coplanar", 200, 5, 0),
    ("duplicates", 150, 4, 1),
    ("clustered", 300, 6, 2),
])
def test_csr_knn_exact_examples(family, n, k, seed):
    """Pinned regressions for the degenerate families (no hypothesis shim
    variance): coplanar clouds, duplicate points, tight clusters."""
    rng = np.random.default_rng(seed)
    pts = _make_cloud(family, n, rng)
    spec = hashgrid.calibrate_spec(pts, k, layout="csr")
    _assert_knn_matches_ckdtree(pts, k, spec)


@pytest.mark.parametrize("n,k,seed", [(512, 6, 0), (300, 4, 3)])
def test_csr_matches_dense_table(n, k, seed):
    """Same spec modulo layout -> identical neighbor sets and masks (the
    dense table is the reference implementation the CSR layout replaced)."""
    verts, faces = geo.car_surface(geo.sample_params(seed))
    pts, _ = sample_surface(verts, faces, n, np.random.default_rng(seed))
    dense = hashgrid.calibrate_spec(pts, k, layout="dense")
    csr = hashgrid.GridSpec(n_points=dense.n_points, k=k,
                            resolution=dense.resolution,
                            neigh_cap=dense.neigh_cap, layout="csr")
    id_, dd, md = hashgrid.knn(jnp.asarray(pts), n, dense)
    ic, dc, mc = hashgrid.knn(jnp.asarray(pts), n, csr)
    np.testing.assert_array_equal(np.asarray(md), np.asarray(mc))
    np.testing.assert_allclose(np.asarray(dd), np.asarray(dc), rtol=1e-6)
    for a, b, m in zip(np.asarray(id_), np.asarray(ic), np.asarray(md)):
        assert set(a[m].tolist()) == set(b[m].tolist())


def test_csr_candidate_lists_match_dense():
    """Candidate *sets* per query agree between layouts (ordering differs:
    dense packs by offset-of-home-cell, CSR by neighbor-cell segment)."""
    pts = np.random.default_rng(7).random((257, 3)).astype(np.float32)
    spec_d = hashgrid.calibrate_spec(pts, 5, n_points=288, layout="dense")
    spec_c = hashgrid.GridSpec(n_points=288, k=5,
                               resolution=spec_d.resolution,
                               neigh_cap=spec_d.neigh_cap, layout="csr")
    buf = np.zeros((288, 3), np.float32)
    buf[:257] = pts
    cd, vd, qd = map(np.asarray,
                     hashgrid.candidate_lists(jnp.asarray(buf), 257, spec_d))
    cc, vc, qc = map(np.asarray,
                     hashgrid.csr_candidate_lists(jnp.asarray(buf), 257,
                                                  spec_c))
    np.testing.assert_array_equal(qd, qc)
    for i in range(257):
        assert set(cd[i][vd[i]].tolist()) == set(cc[i][vc[i]].tolist()), i


def test_csr_huge_grid_o_points_memory():
    """A resolution whose dense table would be ~17M cells x cap (gigabytes)
    runs fine under CSR — nothing is materialized over the grid."""
    rng = np.random.default_rng(11)
    n, k = 4096, 6
    pts = rng.random((n, 3)).astype(np.float32)
    spec = hashgrid.GridSpec(n_points=n, k=k, resolution=(256, 256, 256),
                             neigh_cap=128, layout="csr")
    assert spec.n_cells == 256 ** 3
    idx, d2, mask = hashgrid.knn(jnp.asarray(pts), n, spec)
    # at this resolution cells are far wider than the 4096-point kNN radius?
    # no — verify exactness explicitly instead of assuming
    assert hashgrid.overflow_count(pts, n, spec) == 0
    if hashgrid.max_knn_cell_ratio(pts, n, spec) <= 1.0:
        _assert_knn_matches_ckdtree(pts, k, spec)
    # regardless, every returned neighbor is a real point and masks are sane
    idx, mask = np.asarray(idx), np.asarray(mask)
    assert (idx[mask] >= 0).all() and (idx[mask] < n).all()


def test_calibrate_layouts():
    """calibrate_spec: dense respects the cell budget, CSR may exceed it."""
    verts, faces = geo.car_surface(geo.sample_params(4))
    pts, _ = sample_surface(verts, faces, 2048, np.random.default_rng(4))
    d = hashgrid.calibrate_spec(pts, 6, layout="dense", cell_budget=2.0)
    assert d.n_cells <= max(2.0 * 2048, 27)
    c = hashgrid.calibrate_spec(pts, 6, layout="csr", cell_budget=2.0)
    assert c.layout == "csr"
    # csr ignores the dense budget -> at least as fine a grid
    assert c.n_cells >= d.n_cells


def _car_cloud(n: int, seed: int) -> np.ndarray:
    verts, faces = geo.car_surface(geo.sample_params(seed))
    pts, _ = sample_surface(verts, faces, n, np.random.default_rng(seed))
    return np.asarray(pts, np.float32)


def _border_cloud(n: int, seed: int) -> np.ndarray:
    """Uniform box with a third of the points snapped onto its x and y
    faces, so many queries sit in the grid's border columns."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 3)).astype(np.float32)
    face = rng.integers(0, 4, n)
    snap = rng.random(n) < 1 / 3
    pts[snap & (face == 0), 0] = 0.0
    pts[snap & (face == 1), 0] = 1.0
    pts[snap & (face == 2), 1] = 0.0
    pts[snap & (face == 3), 1] = 1.0
    return pts


def _numpy_csr_rows(cc, cid, n_valid, spec):
    """Plain packing of each query's candidate row: the stable cell-sorted
    point order read over the 9 (dx, dy) columns in ``_XY_OFFSETS`` order,
    z ascending within a column, cut at ``neigh_cap``, self left out."""
    order = np.argsort(cid, kind="stable")
    sorted_cid = cid[order]
    rx, ry, rz = spec.resolution
    rows = []
    for i in range(spec.n_points):
        packed = []
        cx, cy, cz = cc[i]
        for dx, dy in hashgrid._XY_OFFSETS:
            x, y = cx + dx, cy + dy
            if i >= n_valid or not (0 <= x < rx and 0 <= y < ry):
                continue
            for z in range(max(cz - 1, 0), min(cz + 1, rz - 1) + 1):
                c = (x * ry + y) * rz + z
                lo = np.searchsorted(sorted_cid, c, side="left")
                hi = np.searchsorted(sorted_cid, c, side="right")
                packed.extend(order[lo:hi].tolist())
        rows.append([p for p in packed[:spec.neigh_cap] if p != i])
    return rows


@pytest.mark.parametrize("case", ["car", "padded", "overflow", "coplanar",
                                  "border"])
def test_csr_candidate_slot_order(case):
    """Valid candidate ids sit in the same slots, in the same order, as a
    plain numpy packing of the CSR row: top-k breaks distance ties by slot,
    so set equality alone would not pin the served edges."""
    n_points = 1536
    n_valid = 1400 if case == "padded" else n_points
    if case == "coplanar":
        pts = _make_cloud("coplanar", n_points, np.random.default_rng(5))
    elif case == "border":
        pts = _border_cloud(n_points, 6)
    else:
        pts = _car_cloud(n_points, 3)
    spec = hashgrid.calibrate_spec(pts[:n_valid], 6, n_points=n_points)
    if case == "overflow":
        spec = hashgrid.GridSpec(n_points=n_points, k=6,
                                 resolution=spec.resolution, neigh_cap=32)
        assert hashgrid.overflow_count(pts, n_valid, spec) > 0
    buf = pts.copy()
    buf[n_valid:] = 50.0                       # padding: far, and ignored
    valid = jnp.arange(n_points) < n_valid
    cc, cid = map(np.asarray, hashgrid._cells(jnp.asarray(buf), valid, spec))
    if case == "border":
        rx, ry, _ = spec.resolution
        on_border = ((cc[:, 0] == 0) | (cc[:, 0] == rx - 1)
                     | (cc[:, 1] == 0) | (cc[:, 1] == ry - 1))
        assert on_border.sum() > n_points // 4
    cand, cand_valid, qvalid = map(np.asarray, jax.jit(
        hashgrid.csr_candidate_lists, static_argnums=2)(
            jnp.asarray(buf), n_valid, spec))
    assert cand.shape == cand_valid.shape == (n_points, spec.neigh_cap)
    np.testing.assert_array_equal(qvalid, np.arange(n_points) < n_valid)
    expected = _numpy_csr_rows(cc, cid, n_valid, spec)
    for i in range(n_points):
        assert cand[i][cand_valid[i]].tolist() == expected[i], i


def test_csr_candidate_lists_lowering_has_no_scatter():
    """The slot -> sorted-position map is elementwise: the lowered program
    holds no scatter and exactly one (n_points, neigh_cap) gather (the
    candidate ids read from the sorted order)."""
    spec = hashgrid.GridSpec(n_points=4096, k=6, resolution=(24, 12, 10),
                             neigh_cap=256)
    text = jax.jit(hashgrid.csr_candidate_lists, static_argnums=2).lower(
        jax.ShapeDtypeStruct((4096, 3), jnp.float32), jnp.int32(4000),
        spec).as_text()
    assert "stablehlo.scatter" not in text
    gathers = re.findall(r'"stablehlo\.gather".*-> tensor<([0-9x]+)xi32>',
                         text)
    assert gathers.count("4096x256") == 1, gathers
