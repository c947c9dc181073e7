"""Compile-only guards for the Pallas kernels at xmgn-drivaer widths.

Each kernel is compiled for a described TPU v5e (no chip needed) at the
shapes of the 16 384-point serving bucket and must come out as a Mosaic
kernel (``tpu_custom_call``), not the Pallas interpreter. The topology is
described inside a fixture, never at import, so test workers that do not
run this file never load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.graph_build import sample_surface
from repro.data import geometry as geo
from repro.graphx import hashgrid
from repro.kernels.knn.kernel import DEFAULT_BLOCK_Q, knn_topk_call
from repro.kernels.segment_agg import ops as segops
from repro.kernels.segment_agg.kernel import segment_agg_call

BUCKET = 16384                      # smallest bucket chip_smoke.py serves
LEVELS = (BUCKET // 4, BUCKET // 2, BUCKET)
HIDDEN = 512
K = 6


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_text(one_chip, monkeypatch):
    """Compile ``fn`` for the described chip; the kernels choose the
    interpreter from ``jax.default_backend()``, which still reports the
    CPU here, so steer it to the chip's answer for the compile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


def test_segment_agg_compiles_to_mosaic(compiled_text):
    n_edges = sum(2 * K * n for n in LEVELS)     # the bucket's edge slots
    eblk = segops.default_eblk(n_edges, BUCKET)
    n_blocks = -(-BUCKET // 128)
    rows = n_blocks * eblk
    text = compiled_text(
        lambda m, d: segment_agg_call(m, d, n_blocks),
        ((rows, HIDDEN), jnp.float32), ((rows, 1), jnp.int32))
    assert eblk == 5376
    assert "tpu_custom_call" in text


def test_knn_topk_compiles_to_mosaic(compiled_text):
    verts, faces = geo.car_surface(geo.sample_params(0))
    pts, _ = sample_surface(verts, faces, BUCKET, np.random.default_rng(0))
    spec = hashgrid.calibrate_spec(pts, K, n_points=BUCKET)
    cand = jax.eval_shape(
        lambda p: hashgrid.candidate_lists(p, BUCKET, spec)[0],
        jax.ShapeDtypeStruct((BUCKET, 3), jnp.float32))
    c_pad = -(-cand.shape[1] // 128) * 128          # as topk_neighbors pads
    n = -(-BUCKET // DEFAULT_BLOCK_Q) * DEFAULT_BLOCK_Q
    plane = ((n, c_pad), jnp.float32)
    text = compiled_text(
        lambda q, x, y, z, i, v: knn_topk_call(q, x, y, z, i, v, K),
        ((n, 4), jnp.float32), plane, plane, plane,
        ((n, c_pad), jnp.int32), plane)
    assert "tpu_custom_call" in text
