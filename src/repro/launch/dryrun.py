import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run + roofline extraction.

For each (arch x input-shape x mesh): build the real step function
(train_step with Adam update, prefill, or one-token decode), lower it with
ShapeDtypeStruct inputs (no allocation), compile, and record:

  * memory_analysis()  — proof the program fits per-device HBM;
  * cost_analysis()    — per-device HLO flops / bytes accessed;
  * collective bytes   — parsed from the optimized HLO (result-shape bytes of
    all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute);
  * roofline terms     — compute, memory, collective times (seconds) using
    TPU v5e-class constants (197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link ICI).

IMPORTANT (measured, see EXPERIMENTS.md SDry-run): XLA's cost analysis counts
a while-loop (lax.scan) body ONCE regardless of trip count. All cost metrics
are therefore computed by PROBE-DELTA: compile the same config at 1 and 2
scan groups and extrapolate cost(NG) = cost(1) + (NG-1) * (cost(2) - cost(1)).
The full-depth compile is still performed — it is the lowering/memory proof.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] --out results/dryrun
"""


import argparse
import dataclasses
import json
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.configs.base import HW, ModelConfig, ShapeConfig
from repro.launch import costmodel
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.optim.adam import AdamConfig, AdamState, adam_init, adam_update

# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; never allocated)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Model inputs for one step of the given shape."""
    b = shape.global_batch
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        s_text = shape.seq_len
        out = {}
        if cfg.frontend == "vision":
            s_text = shape.seq_len - cfg.n_frontend_tokens
            out["prefix_embeds"] = sds((b, cfg.n_frontend_tokens, cfg.d_model),
                                       jnp.bfloat16)
        if cfg.frontend == "audio":
            out["audio_embeds"] = sds((b, cfg.n_frontend_tokens, cfg.d_model),
                                      jnp.bfloat16)
        out["tokens"] = sds((b, s_text), i32)
        out["labels"] = sds((b, s_text), i32)
        return out
    if shape.kind == "prefill":
        s_text = shape.seq_len
        out = {}
        if cfg.frontend == "vision":
            s_text = shape.seq_len - cfg.n_frontend_tokens
            out["prefix_embeds"] = sds((b, cfg.n_frontend_tokens, cfg.d_model),
                                       jnp.bfloat16)
        if cfg.frontend == "audio":
            out["audio_embeds"] = sds((b, cfg.n_frontend_tokens, cfg.d_model),
                                      jnp.bfloat16)
        out["tokens"] = sds((b, s_text), i32)
        return out
    # decode: ONE new token against a seq_len-sized cache/state
    return {"tokens": sds((b, 1), i32)}


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("pure full-attention architecture: long_500k requires "
                "sub-quadratic attention (DESIGN.md S5)")
    return None


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(api, cfg: ModelConfig):
    opt_cfg = AdamConfig(total_steps=2000)
    accum = max(getattr(cfg, "grad_accum", 1), 1)

    def train_step(params, opt_state, batch):
        if accum == 1:
            loss, grads = jax.value_and_grad(api.train_loss)(params, batch)
        else:
            # gradient aggregation over microbatches (paper SIII-A, applied
            # on the batch axis): activation memory /accum, grads summed
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
                batch)

            def body(carry, mb):
                l_acc, g_acc = carry
                l, g = jax.value_and_grad(api.train_loss)(params, mb)
                return (l_acc + l / accum,
                        jax.tree_util.tree_map(
                            lambda a, b: a + b / accum, g_acc, g)), None

            init = (jnp.zeros(()), jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, p.dtype), params))
            (loss, grads), _ = jax.lax.scan(body, init, micro)
        params, opt_state, metrics = adam_update(opt_cfg, grads, opt_state,
                                                 params)
        return params, opt_state, loss, metrics["grad_norm"]

    return train_step


def make_prefill_step(api):
    def prefill_step(params, batch):
        logits, cache = api.prefill(params, batch)
        return logits[:, -1], cache

    return prefill_step


def make_decode_step(api):
    def decode_step(params, cache, batch, pos):
        logits, cache = api.decode(params, cache, batch, pos)
        return logits[:, -1], cache

    return decode_step


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(.+?)\s(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str, loop_multiplier: int = 1) -> dict:
    """Sum result-shape bytes of collective ops in a per-device HLO module.

    Collectives inside a while-loop BODY computation (the lax.scan over layer
    groups) execute once per trip, but appear once in the text — they are
    multiplied by ``loop_multiplier`` (= n_groups). Validated against a
    2-point layer-count probe-delta in tests/test_dryrun_small.py. Async
    '-start' forms count once ('-done' carries no shape payload)."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    # identify while-body computations
    body_names = set(re.findall(r"body=(%\S+?)[,)]", hlo_text))
    # split into computations: header lines end with '{'; track current name
    cur = None
    in_body = False
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(ENTRY\s+)?(%[\w.\-]+)?\s*\(.*\{$", ls)
        if ls.endswith("{") and ("(" in ls) and (ls.startswith("%")
                                                 or ls.startswith("ENTRY")):
            name = ls.split()[1] if ls.startswith("ENTRY") else ls.split()[0]
            cur = name
            in_body = name in body_names
            continue
        cm = _COLL_RE.search(ls)
        if not cm:
            continue
        shapes_str, op = cm.groups()
        total = 0
        # result may be a tuple (fused gradient all-reduce): sum all elements
        for dt, dims in _SHAPE_RE.findall(shapes_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        if not total:
            continue
        mult = loop_multiplier if in_body else 1
        out[op] += total * mult
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


# ---------------------------------------------------------------------------
# depth probes (scan trip-count correction)
# ---------------------------------------------------------------------------

def n_groups_of(cfg: ModelConfig) -> int:
    if cfg.is_encoder_decoder:
        return cfg.n_layers                      # enc & dec scale together
    if cfg.ssm is not None and cfg.attn_every:
        return cfg.n_layers // cfg.attn_every
    if cfg.ssm is not None:
        return cfg.n_layers // cfg.ssm.slstm_every
    nfd = cfg.moe.first_dense_layers if cfg.moe else 0
    if cfg.layer_pattern == "alt_local_global":
        return (cfg.n_layers - nfd) // 2
    return cfg.n_layers - nfd


def with_groups(cfg: ModelConfig, ng: int) -> ModelConfig:
    if cfg.is_encoder_decoder:
        return cfg.replace(n_layers=ng, encoder_layers=ng)
    if cfg.ssm is not None and cfg.attn_every:
        return cfg.replace(n_layers=ng * cfg.attn_every)
    if cfg.ssm is not None:
        return cfg.replace(n_layers=ng * cfg.ssm.slstm_every)
    nfd = cfg.moe.first_dense_layers if cfg.moe else 0
    if cfg.layer_pattern == "alt_local_global":
        return cfg.replace(n_layers=nfd + 2 * ng)
    return cfg.replace(n_layers=nfd + ng)


# ---------------------------------------------------------------------------
# lower + compile one (cfg, shape, mesh)
# ---------------------------------------------------------------------------

def lower_step(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Returns (lowered, compiled, wall_seconds)."""
    api = registry.get_model(cfg)
    batch = input_specs(cfg, shape)
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    # serving has no optimizer state: use the serve-time sharding policy
    # (TP-only by default) instead of FSDP (SPerf iteration 2)
    if shape.kind == "train":
        mode = cfg.param_sharding
    elif shape.kind == "decode" and getattr(cfg, "decode_param_sharding", ""):
        mode = cfg.decode_param_sharding
    else:
        mode = getattr(cfg, "serve_param_sharding", cfg.param_sharding)
    pspecs = shd.param_specs(params_shape, cfg, mesh, mode=mode)
    psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
    bspecs = shd.batch_specs(cfg, shape, mesh, mode=mode)
    bsh = {k: NamedSharding(mesh, bspecs[k]) for k in batch}
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = make_train_step(api, cfg)
            opt_shape = jax.eval_shape(adam_init, params_shape)
            # ZeRO-1: Adam m/v additionally sharded over 'data'
            ospecs = shd.optimizer_state_specs(params_shape, pspecs, mesh)
            onamed = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), ospecs,
                is_leaf=lambda x: isinstance(x, P))
            osh = AdamState(step=NamedSharding(mesh, P()),
                            mu=onamed, nu=onamed)
            jf = jax.jit(step, in_shardings=(psh, osh, bsh),
                         donate_argnums=(0, 1))
            lowered = jf.lower(params_shape, opt_shape, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(api)
            # the produced KV cache must leave the step SHARDED (data on
            # batch, model on sequence) or it materializes replicated —
            # measured 16 GB/device extra for yi-34b (SPerf iteration 8)
            out_shapes = jax.eval_shape(step, params_shape, batch)
            logits_sh = NamedSharding(
                mesh, shd.batch_specs(cfg, shape, mesh, mode=mode)["tokens"])
            cache_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                shd.cache_specs(cfg, shape, mesh, out_shapes[1]),
                is_leaf=lambda x: isinstance(x, P))
            jf = jax.jit(step, in_shardings=(psh, bsh),
                         out_shardings=(logits_sh, cache_sh))
            lowered = jf.lower(params_shape, batch)
        else:
            step = make_decode_step(api)
            cache_shape = jax.eval_shape(
                lambda: api.empty_cache(shape.global_batch, shape.seq_len))
            cspecs = shd.cache_specs(cfg, shape, mesh, cache_shape)
            csh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, P))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jf = jax.jit(step, in_shardings=(psh, csh, bsh,
                                             NamedSharding(mesh, P())),
                         donate_argnums=(1,))
            lowered = jf.lower(params_shape, cache_shape, batch, pos)
        compiled = lowered.compile()
    return lowered, compiled, time.time() - t0


def cost_metrics(compiled, loop_multiplier: int = 1) -> dict:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text(), loop_multiplier)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": coll,
    }


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             probe_only: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if reason:
        rec["skipped"] = reason
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    # ONE full-depth compile per pair: the lowering + memory proof.
    # Collective bytes: while-body-aware HLO parse (layer-scan collectives
    # x n_groups; validated against a 2-point probe-delta in tests).
    # FLOPs/HBM bytes: analytic cost model (launch/costmodel.py) — XLA
    # cost_analysis counts every while body once, including inner chunk
    # scans, so its raw numbers are recorded only as `hlo_raw`.
    ng_full = n_groups_of(cfg)
    lowered, compiled, secs = lower_step(cfg, shape, mesh)
    mem = compiled.memory_analysis()
    hlo_raw = cost_metrics(compiled)
    coll = collective_bytes(compiled.as_text(), loop_multiplier=ng_full)

    cost = costmodel.step_cost(cfg, shape)
    flops = cost.flops / chips
    hbytes = cost.hbm_bytes / chips

    t_compute = flops / HW.peak_flops
    t_memory = hbytes / HW.hbm_bw
    t_coll = coll["total"] / HW.ici_bw
    dominant = max(
        (("compute", t_compute), ("memory", t_memory), ("collective", t_coll)),
        key=lambda kv: kv[1])[0]

    n_active = registry.active_param_count(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train"
                                   else (shape.seq_len if shape.kind == "prefill" else 1))
    mult = 6 if shape.kind == "train" else 2
    model_flops_pd = mult * n_active * tokens / chips

    rec.update({
        "chips": chips,
        "compile_seconds": round(secs, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "per_device": {
            "flops": flops,
            "hbm_bytes": hbytes,
            "collective_bytes": coll["total"],
            "collective_breakdown": {k: v for k, v in coll.items()
                                     if k not in ("total",)},
            "hlo_raw": hlo_raw,   # cost_analysis as reported (scan bodies 1x)
        },
        "roofline": {
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "dominant": dominant,
        },
        "model_flops_per_device": model_flops_pd,
        "useful_flops_ratio": (model_flops_pd / flops) if flops else None,
        "n_active_params": n_active,
        "n_params": registry.param_count(cfg),
    })
    return rec


# ---------------------------------------------------------------------------
# the paper's own model: X-MGN partitions-as-DDP on the production mesh
# ---------------------------------------------------------------------------

def run_xmgn(multi_pod: bool) -> dict:
    """Dry-run the paper's model at paper scale: a 2M-node 3-level graph
    split into one partition+halo per chip (DDP over ALL mesh axes — the
    paper's scheme has no tensor parallelism), one gradient psum per step."""
    from repro.configs.base import GNNConfig
    from repro.core.distributed_mgn import make_xmgn_ddp_grad_fn
    from repro.models import meshgraphnet as mgn_mod

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cfg = GNNConfig()                      # paper: hidden 512, 15 MP layers
    n_nodes_global = max(cfg.levels)       # 2M points, finest level
    # per-chip partition: owned nodes + 15-hop halo, padded static shapes
    n_owned = n_nodes_global // chips
    pad_nodes = 3 * n_owned                # halo + padding allowance
    pad_edges = pad_nodes * (cfg.k_neighbors + 2)
    P = chips
    sds = jax.ShapeDtypeStruct
    stacked = {
        "node_feats": sds((P, pad_nodes, cfg.node_in), jnp.float32),
        "edge_feats": sds((P, pad_edges, cfg.edge_in), jnp.float32),
        "senders": sds((P, pad_edges), jnp.int32),
        "receivers": sds((P, pad_edges), jnp.int32),
        "targets": sds((P, pad_nodes, cfg.node_out), jnp.float32),
        "loss_mask": sds((P, pad_nodes), jnp.float32),
        "edge_mask": sds((P, pad_edges), jnp.float32),
    }
    denom = float(n_nodes_global * cfg.node_out)
    axes = mesh.axis_names                  # DDP over every axis
    grad_fn = make_xmgn_ddp_grad_fn(mesh, cfg, denom, data_axes=axes)
    params_shape = jax.eval_shape(
        lambda k: mgn_mod.init(k, cfg), jax.random.PRNGKey(0))
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = grad_fn.lower(params_shape, stacked)
        compiled = lowered.compile()
    secs = time.time() - t0
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text(), loop_multiplier=1)
    # analytic flops: encoder + 15 MP layers + decoder, fwd*4 (bwd + remat)
    h, L, ml = cfg.hidden, cfg.n_mp_layers, cfg.mlp_layers
    E, N = pad_edges, pad_nodes
    enc = N * 2 * (cfg.node_in * h + ml * h * h) + \
        E * 2 * (cfg.edge_in * h + ml * h * h)
    per_layer = E * 2 * (3 * h * h + (ml - 1) * h * h) + \
        N * 2 * (2 * h * h + (ml - 1) * h * h) + E * h * 2
    dec = N * 2 * (ml * h * h + h * cfg.node_out)
    flops = 4.0 * (enc + L * per_layer + dec)      # per device (local part)
    hbytes = 3 * 2 * (enc / h)                      # negligible vs activations
    hbytes = 2 * (N + E) * h * 4 * 2 * L + 12 * sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params_shape))
    t_c, t_m, t_x = (flops / HW.peak_flops, hbytes / HW.hbm_bw,
                     coll["total"] / HW.ici_bw)
    dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
              key=lambda kv: kv[1])[0]
    return {
        "arch": "xmgn-drivaer", "shape": "train_2M_3level",
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "compile_seconds": round(secs, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "per_device": {"flops": flops, "hbm_bytes": hbytes,
                       "collective_bytes": coll["total"],
                       "collective_breakdown": {
                           k: v for k, v in coll.items() if k != "total"}},
        "roofline": {"t_compute_s": t_c, "t_memory_s": t_m,
                     "t_collective_s": t_x, "dominant": dom},
        "useful_flops_ratio": 1.0,
        "note": "paper model; ONE gradient psum per step (SIV claim)",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                combos.append((a, s, args.multi_pod))
    else:
        combos.append((args.arch, args.shape, args.multi_pod))

    for arch, shape_name, mp in combos:
        tag = f"{arch}__{shape_name}__{'mp' if mp else 'sp'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print("skip (exists):", tag, flush=True)
            continue
        print("=== dryrun:", tag, flush=True)
        t0 = time.time()
        try:
            if arch == "xmgn-drivaer":
                rec = run_xmgn(mp)
            else:
                rec = run_pair(arch, shape_name, mp)
        except Exception as e:  # record failures; they are bugs to fix
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if mp else "16x16",
                   "error": f"{type(e).__name__}: {e}"}
        rec["wall_seconds"] = round(time.time() - t0, 1)
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, default=str)
        if "error" in rec:
            print("    ERROR:", rec["error"][:500], flush=True)
        elif "skipped" in rec:
            print("    skipped:", rec["skipped"][:120], flush=True)
        else:
            r = rec["roofline"]
            print(f"    ok: dominant={r['dominant']} "
                  f"t_c={r['t_compute_s']:.2e} t_m={r['t_memory_s']:.2e} "
                  f"t_x={r['t_collective_s']:.2e} "
                  f"compile={rec['compile_seconds']}s", flush=True)


if __name__ == "__main__":
    main()
