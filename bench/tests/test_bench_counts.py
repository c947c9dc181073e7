"""Operation, byte and utilisation counts, checked by hand for both
benchmark configs."""
import json
import os

import pytest

from bench import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_mlp_flops_by_hand():
    # xmgn edge MLP: 1536 -> 512 -> 512 -> 512
    assert counts.mlp_flops([1536, 512, 512, 512]) == \
        2 * (1536 * 512 + 512 * 512 + 512 * 512) == 2_621_440
    # MeshGraphNet edge MLP: 384 -> 128 -> 128 -> 128
    assert counts.mlp_flops([384, 128, 128, 128]) == 163_840


@pytest.mark.parametrize("name,edge,node,weights", [
    # per-row edge MLP, node MLP, and the layer's weight bytes
    ("xmgn-drivaer", 2_621_440, 2 * (1024 * 512 + 2 * 512 * 512),
     4 * ((1536 * 512 + 512) + 2 * (512 * 512 + 512) + 2 * 512
          + (1024 * 512 + 512) + 2 * (512 * 512 + 512) + 2 * 512)),
    ("mgn-pfaff2021", 163_840, 2 * (256 * 128 + 2 * 128 * 128),
     4 * ((384 * 128 + 128) + 2 * (128 * 128 + 128) + 2 * 128
          + (256 * 128 + 128) + 2 * (128 * 128 + 128) + 2 * 128)),
])
def test_message_passing_counts_by_hand(name, edge, node, weights):
    cfg = load(name)
    h, n, e = cfg["hidden"], 65536, 688_000
    assert counts.mp_flops(cfg, n, e) == 15 * (e * (edge + h) + n * node)
    assert counts.mp_bytes(cfg, n, e) == 15 * (
        2 * e * h * 4 + 2 * n * h * 4 + 2 * e * 4 + weights)


def test_forward_flops_and_mfu_by_hand():
    cfg = load("xmgn-drivaer")
    n, e = 65536, 688_000
    enc_dec = (n * 2 * (24 * 512 + 2 * 512 * 512)
               + e * 2 * (4 * 512 + 2 * 512 * 512)
               + n * 2 * (2 * 512 * 512 + 512 * 4))
    total = enc_dec + counts.mp_flops(cfg, n, e)
    assert counts.forward_flops(cfg, n, e) == total
    # about 2.9e13 operations: 0.15 s at a v5e's 197e12 bf16 peak
    assert 2.8e13 < total < 3.0e13
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the roofline's least time is the larger bound: operations here
    mp_f, mp_b = counts.mp_flops(cfg, n, e), counts.mp_bytes(cfg, n, e)
    assert counts.least_seconds(mp_f, mp_b, peaks) == mp_f / 197e12
    mgn = load("mgn-pfaff2021")
    e1 = 400_000
    f1, b1 = counts.mp_flops(mgn, n, e1), counts.mp_bytes(mgn, n, e1)
    # at hidden 128 the layers are bound by bytes
    assert counts.least_seconds(f1, b1, peaks) == b1 / 819e9
