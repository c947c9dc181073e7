"""The plain reference against the program at a tiny size: the normals of
the served cloud, the same edge set, the same fields; and the weights in the
program's layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, traffic

CFG = {"name": "tiny", "hidden": 16, "n_mp_layers": 3, "mlp_layers": 2,
       "act": "silu", "k_neighbors": 6, "n_levels": 3, "node_in": 24,
       "edge_in": 4, "node_out": 4, "fourier_freqs": [2.0, 4.0, 8.0],
       "matmul_precision": "highest"}


def cloud(n=512, seed=(2**40 + 3, 5)):
    """A cloud drawn as the server draws it: (verts, faces, (points,
    normals))."""
    from repro.core.graph_build import sample_surface
    v, f = traffic.car_surface(traffic.car_params(3), 64, 32)
    return v, f, sample_surface(v, f, n, np.random.default_rng(seed))


def program_config(cfg):
    from repro.configs.base import GNNConfig
    return GNNConfig(hidden=cfg["hidden"], n_mp_layers=cfg["n_mp_layers"],
                     mlp_layers=cfg["mlp_layers"], act=cfg["act"],
                     remat=False)


def test_normals_of_the_triangles_points_lie_on():
    v, f, (pts, nrm) = cloud(4096)
    got, _ = reference.point_normals(v, f, pts)
    diff = np.linalg.norm(got - nrm, axis=1)
    # a point within rounding of an edge may take its neighbour's normal
    assert np.mean(diff < 1e-6) > 0.995
    assert diff.max() < 0.2
    # points that the nearest centroids miss are found all the same
    got_narrow, n_far = reference.point_normals(v, f, pts, k=(2, 4))
    assert n_far > 0
    np.testing.assert_array_equal(got_narrow, got)


def test_graph_equals_the_programs_edge_sets():
    from repro.core.multiscale import multiscale_edges
    from repro.graphx.pipeline import device_multiscale_edges
    _, _, (pts, _) = cloud()
    levels = reference.level_sizes(512, 3)
    s, r = reference.multiscale_graph(pts, levels, 6)
    mine = set(zip(s.tolist(), r.tolist()))
    assert len(mine) == len(s)
    hs, hr, _ = multiscale_edges(pts, levels, 6)
    assert mine == set(zip(hs.tolist(), hr.tolist()))
    ds, dr, _ = device_multiscale_edges(pts, levels, 6)
    assert mine == set(zip(ds.tolist(), dr.tolist()))


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_forward_matches_meshgraphnet_apply(act):
    from repro.models import meshgraphnet
    cfg = dict(CFG, act=act)
    _, _, (pts, nrm) = cloud()
    params = reference.init_params(cfg, 2**35 + 1)
    nf, ef, s, r = reference.graph_inputs(cfg, pts, nrm)
    got = reference.forward(params, cfg, nf, ef, s, r)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(meshgraphnet.apply(
            params, program_config(cfg), jnp.asarray(nf), jnp.asarray(ef),
            jnp.asarray(s), jnp.asarray(r)))
    scale = reference.rms_gap(want, 0.0)
    assert reference.rms_gap(got, want) < 1e-5 * scale
    # the bfloat16 control departs far more
    low = reference.forward(params, cfg, nf, ef, s, r, jnp.bfloat16)
    assert reference.rms_gap(low, want) > 1e-3 * scale


def test_weights_have_the_programs_layout():
    from repro.models import meshgraphnet
    mine = reference.init_params(CFG, 7)
    theirs = meshgraphnet.init(jax.random.PRNGKey(0), program_config(CFG))
    shape = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    assert shape(mine) == shape(theirs)
    again = reference.init_params(CFG, 7)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(again)))


def test_processor_ln_scale_sets_the_residual_updates():
    base = reference.init_params(CFG, 9)
    small = reference.init_params(dict(CFG, processor_ln_scale=0.1), 9)
    for mlp in ("proc_edge", "proc_node"):
        for k in ("scale", "bias"):
            np.testing.assert_allclose(small[mlp]["ln"][k],
                                       0.1 * base[mlp]["ln"][k], rtol=1e-6)
    for mlp in ("node_encoder", "edge_encoder"):
        np.testing.assert_array_equal(small[mlp]["ln"]["scale"],
                                      base[mlp]["ln"]["scale"])
