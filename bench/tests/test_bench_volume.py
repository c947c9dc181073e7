"""The volume cell on the CPU at a tiny size: its operation counts agree
with XLA's, its check passes the program and fails the control (the
reference wholly in bfloat16), it rebuilds the served cloud and flags
another, it samples blocks in every stratum, its rate counts whole service
periods, its four readers read a run, and it stops at once on a program
that serves no volume family."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts_unet, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


CFG = load("configs", "xunet3d-drivaer.json")
LIMITS = load("limits", "xunet-serve-sweep.json")
# a 12 x 4 x 4 m box of 25 cm voxels around the pool's cars, two levels,
# three slabs of a 40-plane window: wide enough that bfloat16 shows
TINY = {"grid": [48, 16, 16], "voxel": 0.25, "box_lo": [-3.5, -2.0, -1.5],
        "base_channels": 16, "depth": 2, "halo": 12, "n_partitions": 3,
        "sdf_band": 2, "sdf_prefix": 64, "max_points": 512}


def cell():
    return run.load_module("cells", "volume.py")


SEED = 2**34 + 11


def tiny_mix():
    return dict(load("traffic", "volume-sweep.json"), points=512, nu=64,
                nv=32, pool_ids=[1, 2, 3], check_sample=2)


def tiny_run(control: bool = False, seconds: float = 1.0):
    return cell().run(dict(CFG, **TINY), tiny_mix(), SEED, seconds, False,
                      time.perf_counter(), "", control=control)


@pytest.mark.parametrize("depth,shape", [(2, (8, 12, 4)), (3, (8, 8, 12))])
def test_conv_flops_agree_with_xla_cost_analysis(depth, shape):
    cfg = dict(CFG, base_channels=8, depth=depth)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1, 1), "SAME",
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))

    total = 0
    for s, k, cin, cout in counts_unet.conv_layers(cfg, shape):
        c = jax.jit(conv).lower(
            jax.ShapeDtypeStruct((1, *s, cin), jnp.float32),
            jax.ShapeDtypeStruct((k, k, k, cin, cout), jnp.float32)).compile()
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        assert ca["flops"] == counts_unet.conv_flops(s, k, cin, cout)
        total += ca["flops"]
    assert total == counts_unet.forward_flops(cfg, shape)


def test_published_counts():
    # about 1.5 MFLOP a voxel; a geometry's U-Net operations are the
    # domain's times the planes computed over the planes owned
    n, owned, window = counts_unet.slab_windows(CFG)
    assert n * owned >= 800 and window == min(owned + 80, 800)
    per_voxel = counts_unet.domain_flops(CFG) / (800 * 304 * 224)
    assert 1.4e6 < per_voxel < 1.6e6
    assert counts_unet.served_flops(CFG) == pytest.approx(
        counts_unet.domain_flops(CFG) * n * window / 800, rel=0.01)


def test_program_passes_and_bf16_control_fails():
    out = tiny_run(control=True)
    correct, checks = run.verdict(out["numbers"], LIMITS)
    assert correct and set(checks) == set(LIMITS)
    assert out["numbers"]["missing_answers"] == 0
    assert out["numbers"]["fields_rms_err"] < LIMITS["fields_rms_err"] / 10
    # the served cloud is the one the reference rebuilds, to the bit
    assert out["numbers"]["cloud_max_err"] == 0.0
    correct_c, checks_c = run.verdict(out["control"], LIMITS)
    assert not correct_c
    assert checks_c["fields_rms_err"]["value"] > LIMITS["fields_rms_err"]
    assert out["rate"] > 0 and out["setup_s"] > 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    # 3 slabs of 16 owned planes in 40-plane windows per answer
    v = out["voxels"]
    assert v["owned"] > 0 and v["owned"] % (48 * 16 * 16) == 0
    assert v["computed"] * 48 == v["owned"] * 3 * 40


def test_check_flags_a_cloud_other_than_the_request_definition():
    """An answer served on another seed's cloud fails ``cloud_max_err``."""
    from bench import reference_unet, traffic
    from bench.cells import serve
    cfg, mix = dict(CFG, **TINY), tiny_mix()
    pool = traffic.geometry_pool(mix)
    params = reference_unet.init_params(cfg, SEED)
    pts, _ = reference_unet.sample_cloud(*pool[0], mix["points"], SEED + 1, 4)

    class Served:
        points, error = pts, None
        fields = np.zeros((*cfg["grid"], 4), np.float32)

    out = cell().check(cfg, mix, pool, params, SEED,
                       [serve.Answer(4, 0, 1.0, Served())])
    assert out["checked"] == 1
    assert out["numbers"]["cloud_max_err"] > 100 * LIMITS["cloud_max_err"]
    correct, checks = run.verdict(out["numbers"], LIMITS)
    assert not correct
    assert checks["cloud_max_err"]["value"] > LIMITS["cloud_max_err"]


@pytest.mark.parametrize("grid,depth,n_blocks,want", [
    ([800, 304, 224], 3, 6, 6), ([48, 16, 16], 2, 6, 2),
    ([96, 16, 16], 2, 3, 3)])
def test_check_blocks_lie_one_in_each_stratum(grid, depth, n_blocks, want):
    mod = cell()
    cfg = dict(CFG, grid=grid, depth=depth)
    X, align = grid[0], 2 ** (depth - 1)
    for seed in range(20):
        starts = mod.block_starts(cfg, n_blocks,
                                  np.random.default_rng(seed))
        assert len(starts) == want
        for k, x0 in enumerate(starts):
            assert x0 % align == 0 and 0 <= x0 <= X - mod.CHECK_OWNED
            assert k * X // want <= x0 + mod.CHECK_OWNED - 1
            assert x0 < (k + 1) * X // want


def test_rate_counts_whole_service_periods():
    from bench.cells import serve
    mod = cell()

    class Ready:
        error = None

        def __init__(self, latency_s):
            self.latency_s = latency_s

    # ready 2, 11.5, 21 and 30.5 s after the window opened, three taken in
    # one burst as the worker publishes a plan's answers together: three
    # whole periods of 9.5 s whenever they were taken
    sent = {0: -30.0, 1: -25.0, 2: -20.0, 3: 2.0}
    answers = [serve.Answer(rid, 0, 21.0, Ready(t - sent[rid]))
               for rid, t in zip(range(3), (2.0, 11.5, 21.0))]
    answers.append(serve.Answer(3, 0, 30.5, Ready(28.5)))
    ready = mod.ready_times(answers, sent)
    assert ready == pytest.approx([2.0, 11.5, 21.0, 30.5])
    assert mod.period_rate(ready) == pytest.approx(1 / 9.5)
    # an answer that never came is not a period
    assert mod.ready_times(answers + [serve.Answer(9, 0)], sent) == ready
    assert mod.period_rate(ready[:1]) is None


def test_client_notes_when_each_request_was_sent():
    mod = cell()

    class Server:
        def submit(self, verts, faces, n):
            return 7

        def result(self, rid, timeout=None):
            return (rid, timeout)

    client = mod.Client(Server())
    t0 = time.perf_counter()
    assert client.submit(None, None, 16) == 7
    assert t0 <= client.sent[7] <= time.perf_counter()
    assert client.result(7, timeout=2.0) == (7, 2.0)


def synthetic_ctx(with_program: bool = True):
    peaks = load("peaks.json")["TPU v5 lite"]
    paths = {"infer/while/body/unet/encode/convolution": 2.0,
             "infer/while/body/unet/gate/multiply": 0.25,
             "infer/while/body/unet/decode/convolution": 1.5,
             "infer/while/body/unet/head/convolution": 0.25,
             "infer/graphx/volume_features/while/body/gather": 0.4,
             "infer/while/body/graphx/volume_features/sin": 0.2}
    trace = {"path_s": paths if with_program else {"infer/other/add": 1.0},
             "executions": 2, "busy_s": 9.0, "window_s": 10.0}
    run_ = {"rate": 0.25, "rows_per_execution": 1, "stages": {}}
    if with_program:
        run_["voxels"] = {"owned": 2 * 800 * 304 * 224,
                          "computed": 2 * 25 * 112 * 304 * 224}
    return {"run": run_, "config": CFG, "traffic": {}, "peaks": peaks,
            "trace": trace}


def read(name, ctx):
    return run.load_module("metrics", f"{name}.py").read(ctx)


def test_readers_read_a_synthetic_run():
    ctx = synthetic_ctx()
    peak = ctx["peaks"]["flops_per_s"]
    least = counts_unet.served_flops(CFG) / peak       # compute-bound
    assert least > counts_unet.served_bytes(CFG) / ctx["peaks"][
        "hbm_bytes_per_s"]
    assert read("unet_roofline.volume", ctx) == pytest.approx(
        100 * least / 2.0)
    assert read("sdf_ms.volume", ctx) == pytest.approx(300.0)
    assert read("halo_share.volume", ctx) == pytest.approx(
        100 * (1 - 800 / (25 * 112)))
    assert read("mfu.volume", ctx) == pytest.approx(
        100 * counts_unet.domain_flops(CFG) * 0.25 / peak)


def test_readers_find_nothing_in_a_run_without_the_volume_program():
    ctx = synthetic_ctx(with_program=False)
    for name in ("unet_roofline.volume", "sdf_ms.volume",
                 "halo_share.volume"):
        assert read(name, ctx) is None


def test_cell_stops_at_once_without_a_unet_family(monkeypatch):
    from repro.launch import serve_gnn
    monkeypatch.setattr(serve_gnn.GNNServer, "FAMILIES", ("gnn",))
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="unet"):
        tiny_run()
    assert time.perf_counter() - t0 < 5.0
