"""Cross-check the analytic cost model against XLA's cost_analysis on
configurations with NO hidden loop iterations (single layer group, sequence
short enough that attention doesn't chunk): the two must agree to ~2x.
This guards against systematic counting errors (madd conventions, missing
terms, layer multipliers) in launch/costmodel.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch import costmodel
from repro.models import registry


def _hlo_flops(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    ca = c.cost_analysis() or {}
    return float(ca.get("flops", 0.0))


@pytest.mark.parametrize("arch", ["granite-3-8b", "gemma2-9b",
                                  "deepseek-moe-16b"])
def test_forward_flops_match_hlo(arch):
    cfg = get_config(arch).reduced()
    # single scan group, short sequence => no hidden trip counts
    if cfg.layer_pattern == "alt_local_global":
        cfg = cfg.replace(n_layers=2)
    elif cfg.moe is not None:
        cfg = cfg.replace(n_layers=(cfg.moe.first_dense_layers or 0) + 1)
    else:
        cfg = cfg.replace(n_layers=1)
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = 2, 64
    shape = ShapeConfig("probe", seq_len=s, global_batch=b, kind="prefill")
    batch = {"tokens": jnp.zeros((b, s), jnp.int32)}

    def fwd(p, batch):
        logits, _ = api.prefill(p, batch)
        return logits

    hlo = _hlo_flops(fwd, params, batch)
    analytic = costmodel.fwd_flops(cfg, shape)
    assert hlo > 0 and analytic > 0
    ratio = analytic / hlo
    # prefill also builds the cache (not in the analytic model) and XLA
    # counts some elementwise ops we ignore — agree within 2.5x
    assert 0.4 < ratio < 2.5, (arch, analytic, hlo, ratio)


def test_train_multiplier_direction():
    """Train flops must exceed forward flops by ~3-4x (bwd + remat)."""
    cfg = get_config("granite-3-8b")
    shape_t = ShapeConfig("t", 4096, 256, "train")
    c = costmodel.step_cost(cfg, shape_t)
    assert 2.9 * c.fwd_flops <= c.flops <= 4.1 * c.fwd_flops


def test_decode_cheaper_than_prefill():
    cfg = get_config("gemma2-9b")
    dec = costmodel.step_cost(cfg, ShapeConfig("d", 32768, 128, "decode"))
    pre = costmodel.step_cost(cfg, ShapeConfig("p", 32768, 32, "prefill"))
    assert dec.flops < pre.flops / 100     # one token vs 32k tokens
    # but decode HBM traffic is cache-dominated, not ~0
    assert dec.hbm_bytes > registry.param_count(cfg)


def test_moe_flops_scale_with_topk_not_experts():
    cfg = get_config("qwen3-moe-30b-a3b")
    shape = ShapeConfig("t", 4096, 256, "train")
    base = costmodel.fwd_flops(cfg, shape)
    more_experts = cfg.replace(moe=dataclasses.replace(
        cfg.moe, n_experts=2 * cfg.moe.n_experts))
    more_topk = cfg.replace(moe=dataclasses.replace(
        cfg.moe, top_k=2 * cfg.moe.top_k))
    # doubling experts only adds router flops (<2%); doubling top_k ~doubles
    # the routed-FFN term
    assert costmodel.fwd_flops(more_experts, shape) < 1.1 * base
    assert costmodel.fwd_flops(more_topk, shape) > 1.25 * base
