"""Training driver.

Two modes:
* GNN (the paper): partitioned X-MeshGraphNet training with halo regions and
  gradient aggregation on synthetic DrivAerML-proxy data. The stacked (P, ...)
  partition batch is processed by a single-device ``lax.scan`` when one device
  is visible, and partition-parallel under ``shard_map`` when more are: each
  device scans its local partitions and gradients are combined with exactly
  ONE psum per step (paper SIII-A — equivalence to full-graph training is
  pinned by ``tests/test_train_equivalence.py``). Training graphs come from
  the host cKDTree build (``--graph-source host``) or the device-resident
  ``repro.graphx`` pipeline serving uses (``--graph-source graphx``).
* LLM: any assigned architecture (reduced or full config) on synthetic token
  streams.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch xmgn-drivaer --reduced \
      --steps 100 --samples 8
  PYTHONPATH=src python -m repro.launch.train --arch xmgn-drivaer --reduced \
      --steps 100 --graph-source graphx --shard-devices 4
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --reduced \
      --steps 20
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.ckpt import compile_cache
from repro.configs import get_config
from repro.configs.base import GNNConfig
from repro.core import distributed_mgn as dmgn
from repro.core.gradient_aggregation import scan_aggregate_gradients
from repro.data import pipeline as pipe
from repro.data.tokens import token_batches
from repro.launch.sharding import mesh_for_shards, shard_count_for, shard_put
from repro.models import meshgraphnet as mgn
from repro.models import registry
from repro.optim.adam import AdamConfig, AdamState, adam_init, adam_update
from repro.resilience import faults
from repro.telemetry import Telemetry, default_latency_buckets

# training-loop stages whose wall time lands in the metrics registry as
# ``train_stage_<name>_seconds`` histograms (benchmarks/bench_train.py
# reports them as the per-stage breakdown)
TRAIN_STAGES = ("data", "partition", "prepare", "step", "eval", "checkpoint")


def _stage_hists(tel: Telemetry) -> dict:
    return {s: tel.metrics.histogram(
        f"train_stage_{s}_seconds",
        help=f"wall seconds spent in the '{s}' training stage",
        buckets=default_latency_buckets())
        for s in TRAIN_STAGES}


def make_gnn_step_fn(cfg: GNNConfig, opt_cfg: AdamConfig, mesh=None,
                     axis: str = "data"):
    """One jitted optimizer step over a stacked (P, ...) partition batch.

    ``mesh=None`` is the single-device scan path — bit-identical to the
    pre-sharding trainer (same scan, same adam call, checkpoints compatible).
    With a mesh, the partition axis is sharded over ``axis``: each device
    scans its local partitions and the per-device sums meet in exactly one
    ``psum`` (``distributed_mgn.make_xmgn_ddp_grad_fn``); the optimizer then
    runs on the replicated summed gradients, so parameters stay identical on
    every device.

    Returns ``step(params, opt, stacked, denom) -> (params, opt, loss,
    grad_norm, skipped)``. On the sharded path ``stacked`` must carry a
    ``"denom"`` leaf of shape (P,) (see :func:`prepare_gnn_batch`) and the
    ``denom`` argument is ignored — a traced scalar cannot cross into
    ``shard_map`` as a closure without re-tracing per sample.

    Nonfinite guard (``cfg.nonfinite_guard``, default on): when the loss
    or any gradient leaf is NaN/Inf the optimizer update is SKIPPED — the
    returned params and Adam state are the inputs, bit for bit, and
    ``skipped`` is True. One poisoned batch costs one step instead of the
    whole run. On a finite step the guard is an exact-select no-op: the
    updated values pass through unchanged (the bitwise single-device
    equivalence in ``tests/test_train_equivalence.py`` still holds).
    """
    guard = bool(getattr(cfg, "nonfinite_guard", True))

    def guarded_update(loss, grads, opt, params):
        new_params, new_opt, metrics = adam_update(opt_cfg, grads, opt,
                                                   params)
        if not guard:
            return (new_params, new_opt, loss, metrics["grad_norm"],
                    jnp.asarray(False))
        finite = jnp.isfinite(loss) & jax.tree_util.tree_reduce(
            jnp.logical_and,
            jax.tree_util.tree_map(
                lambda g: jnp.all(jnp.isfinite(g)), grads),
            jnp.asarray(True))

        def keep(new, old):
            return jnp.where(finite, new, old)

        params = jax.tree_util.tree_map(keep, new_params, params)
        opt = jax.tree_util.tree_map(keep, new_opt, opt)
        return params, opt, loss, metrics["grad_norm"], ~finite

    if mesh is None:
        @jax.jit
        def step_fn(params, opt, stacked, denom):
            def grad_fn(p, b):
                return jax.value_and_grad(
                    lambda q: mgn.loss_fn(q, cfg, b, denom=denom))(p)
            loss, grads = scan_aggregate_gradients(grad_fn, params, stacked)
            return guarded_update(loss, grads, opt, params)
        return step_fn

    grad_call = dmgn.make_xmgn_ddp_grad_fn(mesh, cfg, denom=None,
                                           data_axes=(axis,), jit=False)

    @jax.jit
    def step_fn(params, opt, stacked, denom):
        loss, grads = grad_call(params, stacked)
        return guarded_update(loss, grads, opt, params)
    return step_fn


def prepare_gnn_batch(ps: pipe.PartitionedSample, mesh=None,
                      axis: str = "data"):
    """Device placement for one partitioned sample: ``(stacked, denom)``.

    Single device: plain host->device transfer (the seed trainer's layout).
    Sharded: the per-sample loss denominator is repeated into a (P,)
    ``"denom"`` leaf so it shards alongside the partitions (one compiled
    step covers samples of different sizes), and the batch is placed with
    its partition axis sharded over the mesh.
    """
    if mesh is None:
        return (jax.tree_util.tree_map(jnp.asarray, ps.stacked),
                jnp.asarray(ps.denom))
    stacked = dict(ps.stacked)
    n_parts = stacked["senders"].shape[0]
    stacked["denom"] = np.full((n_parts,), ps.denom, np.float32)
    return shard_put(stacked, mesh, axis), jnp.asarray(ps.denom)


def train_gnn(cfg: GNNConfig, steps: int, n_samples: int,
              ckpt_path: str | None = None, log_every: int = 10,
              agg_impl: str | None = None,
              graph_source: str | None = None,
              shard_devices: Optional[int] = None,
              telemetry: Optional[Telemetry] = None,
              ckpt_every: int = 0, resume: str | None = None,
              opt_total_steps: Optional[int] = None,
              keep_ckpts: Optional[int] = None,
              noise_std: Optional[float] = None):
    """Train X-MeshGraphNet on partitioned synthetic DrivAerML-proxy data.

    ``shard_devices`` caps the partition-parallel width (``None`` = use as
    many visible devices as divide ``cfg.n_partitions``; ``1`` forces the
    single-device scan path). ``graph_source`` overrides
    ``cfg.graph_source`` for the training-graph build.

    Checkpointing: ``ckpt_path`` is written after the final step and —
    with ``ckpt_every > 0`` — every that-many steps, on a background
    thread (:class:`repro.ckpt.AsyncCheckpointer`: the loop never blocks
    on checkpoint I/O; write seconds land in the ``checkpoint`` stage
    histogram). The checkpoint carries params, the full Adam state
    (step/mu/nu), the loop step, the LR-schedule horizon and the
    normalizer stats, so ``resume=<path>`` continues the optimizer
    trajectory EXACTLY: training N steps equals training k, crashing, and
    resuming for the remaining N-k (pinned by
    ``tests/test_train_resume.py``). ``opt_total_steps`` decouples the
    cosine-schedule horizon from this invocation's ``steps`` — a resumed
    run keeps the original horizon (stored in the checkpoint) so the LR
    at step t is identical to the uninterrupted run's.

    ``noise_std`` (default ``cfg.noise_std``; 0 = off) adds MGN-style
    training noise: zero-mean gaussian perturbation of the node features
    each step, so the model learns to damp the distribution shift its own
    autoregressive rollout errors induce (Pfaff et al. 2020 §A.3 — the
    rollout-stability trick the transient-rollout engine relies on).
    Draws are seeded by the GLOBAL step, so a crash+resume reproduces the
    identical noise sequence; ``noise_std=0`` is a bitwise no-op (pinned
    by ``tests/test_rollout.py``).

    ``telemetry`` (or the config's ``telemetry``/``trace_dir`` knobs)
    records the loop's stage timings: every stage lands in the metrics
    registry as a ``train_stage_<name>_seconds`` histogram regardless of
    the enabled flag, and additionally as tracer spans (``data``,
    ``partition``, ``step`` with ``trace_id="step-<it>"``, nested
    ``prepare``, ``checkpoint``) when the span tracer is on.
    """
    if agg_impl is not None:
        cfg = cfg.replace(agg_impl=agg_impl)
    if graph_source is not None:
        cfg = cfg.replace(graph_source=graph_source)
    # persistent XLA compile cache: a restarted/resumed trainer re-traces
    # its step program but loads the backend executable from disk
    if cfg.compile_cache_dir:
        compile_cache.enable(cfg.compile_cache_dir)
    tel = telemetry if telemetry is not None else Telemetry.from_config(cfg)
    hists = _stage_hists(tel)
    loss_gauge = tel.metrics.gauge("train_loss",
                                   help="most recent training loss")
    steps_ctr = tel.metrics.counter("train_steps_total",
                                    help="optimizer steps taken")
    with tel.span("data", scope="train", hist=hists["data"],
                  n_samples=n_samples):
        train, test, norm_in, norm_out = pipe.build_dataset(cfg, n_samples)
    # one partitioning pass per sample + common padding so one jit covers all
    with tel.span("partition", scope="train", hist=hists["partition"],
                  n_samples=len(train)):
        psamples = pipe.partition_samples(cfg, train, norm_in, norm_out)

    params = mgn.init(jax.random.PRNGKey(0), cfg)
    start_step = 0
    restored = None
    if resume:
        # retention-aware restore: a corrupt newest checkpoint (crash mid
        # write, disk damage) falls back to the previous intact one from
        # the --keep-ckpts window instead of killing the resume
        restored, used_path, skipped_paths = ckpt.restore_with_fallback(
            resume)
        for p in skipped_paths:
            print(f"WARNING: skipped corrupt checkpoint {p}", flush=True)
        if used_path != resume:
            print(f"resuming from retained fallback {used_path}", flush=True)
        if "params" not in restored:
            raise ckpt.CheckpointError(
                f"{used_path!r} is not a training checkpoint (no 'params')")
        params = restored["params"]
    if opt_total_steps is None:
        # a resumed run keeps the original cosine horizon so the LR
        # trajectory matches the uninterrupted run's
        opt_total_steps = int(restored["opt_total_steps"]) \
            if restored and "opt_total_steps" in restored else steps
    opt_cfg = AdamConfig(total_steps=int(opt_total_steps))
    opt = adam_init(params)
    if restored is not None and "opt" in restored:
        o = restored["opt"]
        opt = AdamState(step=jnp.asarray(o["step"], jnp.int32),
                        mu=o["mu"], nu=o["nu"])
        start_step = int(restored.get("step", 0))
        print(f"resumed {resume} at step {start_step} "
              f"(schedule horizon {opt_cfg.total_steps})", flush=True)

    def ckpt_tree(params, opt, next_step):
        return {"params": params,
                "opt": {"step": opt.step, "mu": opt.mu, "nu": opt.nu},
                "step": int(next_step),
                "opt_total_steps": int(opt_cfg.total_steps),
                "norm_in": vars(norm_in), "norm_out": vars(norm_out)}

    n_shards = shard_count_for(cfg.n_partitions, limit=shard_devices)
    mesh = mesh_for_shards(n_shards) if n_shards > 1 else None
    if mesh is not None:
        print(f"partition-parallel: {cfg.n_partitions} partitions over "
              f"{n_shards} devices ({cfg.n_partitions // n_shards} per "
              "device, one grad psum per step)", flush=True)
    step_fn = make_gnn_step_fn(cfg, opt_cfg, mesh=mesh)

    if keep_ckpts is None:
        keep_ckpts = int(getattr(cfg, "keep_ckpts", 0))
    if noise_std is None:
        noise_std = float(getattr(cfg, "noise_std", 0.0))
    skip_ctr = tel.metrics.counter(
        "train_nonfinite_steps_total",
        help="optimizer steps skipped on a nonfinite loss/grad")
    nonfinite_steps = 0
    losses = []
    t_first = 0.0
    t_warm = 0.0
    writer = ckpt.AsyncCheckpointer(on_write=hists["checkpoint"].observe)
    for it in range(start_step, steps):
        # stage one sample per step: at paper scale a padded partition batch
        # is GBs, so keeping every sample device-resident would defeat the
        # single-accelerator mode. Indexing by the GLOBAL step keeps the
        # sample sequence identical across a crash+resume.
        t0 = time.time()
        with tel.span("step", trace_id=f"step-{it}", scope="train", it=it):
            tp0 = time.perf_counter()
            with tel.span("prepare", scope="train"):
                stacked, denom = prepare_gnn_batch(
                    psamples[it % len(psamples)], mesh)
                if faults.active():
                    # chaos: poison this step's node features so the
                    # nonfinite skip-step guard has something to catch
                    nf = np.asarray(stacked["node_feats"])
                    bad = faults.corrupt("train.batch", nf)
                    if bad is not nf:     # corrupt returns arr iff unfired
                        stacked = dict(stacked)
                        stacked["node_feats"] = jnp.asarray(bad)
                if noise_std > 0.0:
                    # MGN rollout-stability noise, seeded by the global
                    # step (resume-reproducible)
                    nf = np.asarray(stacked["node_feats"])
                    nrng = np.random.default_rng((0xF10A7, it))
                    stacked = dict(stacked)
                    stacked["node_feats"] = jnp.asarray(
                        nf + nrng.standard_normal(
                            nf.shape).astype(nf.dtype) * noise_std)
            tp1 = time.perf_counter()
            params, opt, loss, gnorm, skipped = step_fn(
                params, opt, stacked, denom)
            losses.append(float(loss))      # blocks until the step finishes
            if bool(skipped):
                nonfinite_steps += 1
                skip_ctr.inc()
                tel.tracer.record_span("nonfinite_skip", tp1,
                                       time.perf_counter(), it=it)
                print(f"step {it:5d} SKIPPED: nonfinite loss/grads "
                      f"(loss {float(loss)}, {nonfinite_steps} skipped so "
                      "far) — params and Adam state unchanged", flush=True)
        hists["prepare"].observe(tp1 - tp0)
        hists["step"].observe(time.perf_counter() - tp1)
        loss_gauge.set(float(loss))
        steps_ctr.inc()
        if (ckpt_path and ckpt_every > 0 and (it + 1) % ckpt_every == 0
                and it + 1 < steps):
            # async: snapshot to host, write on the ckpt-writer thread —
            # the loop only ever waits for the PREVIOUS write. With
            # keep_ckpts > 0 periodic saves go to step-tagged siblings
            # (<path>.stepNNNNNNNN) and the window is pruned — a corrupt
            # newest file leaves an intact fallback for --resume.
            with tel.span("checkpoint", scope="train", path=ckpt_path, it=it):
                if keep_ckpts > 0:
                    writer.save(ckpt.retained_path(ckpt_path, it + 1),
                                ckpt_tree(params, opt, it + 1))
                    # the in-flight write is not on disk yet; prunable
                    # files are all from completed earlier saves
                    ckpt.prune_retained(ckpt_path, keep_ckpts)
                else:
                    writer.save(ckpt_path, ckpt_tree(params, opt, it + 1))
        dt = time.time() - t0
        if it == start_step:
            t_first = dt                   # compile + first execution
        else:
            t_warm += dt
        if it % log_every == 0:
            # warm s/step excludes the first step: folding compile into the
            # average overstates steady-state step time for the whole run
            timing = (f"first+compile {t_first:.2f}s" if it == start_step
                      else f"{t_warm / (it - start_step):.2f}s/step warm, "
                      f"first+compile {t_first:.2f}s")
            print(f"step {it:5d} loss {float(loss):.5f} "
                  f"gnorm {float(gnorm):.3f} ({timing})", flush=True)
    writer.wait()                          # surface any background failure
    if ckpt_path:
        with tel.span("checkpoint", scope="train", hist=hists["checkpoint"],
                      path=ckpt_path):
            ckpt.save(ckpt_path, ckpt_tree(params, opt, steps))
    return params, losses, (train, test, norm_in, norm_out)


def predict_gnn(cfg: GNNConfig, params, samples, norm_in, norm_out):
    """Denormalized full-cloud predictions, one compiled forward for all
    samples.

    Samples are partitioned with COMMON padding (``partition_samples``), so
    the vmapped forward jit-compiles once and is reused — the old per-sample
    padding dispatched a fresh eager vmap per sample (and would have
    recompiled per shape if jitted). Owned-node predictions are reassembled
    to global order and decoded with ``norm_out``.
    """
    psamples = pipe.partition_samples(cfg, samples, norm_in, norm_out)

    @jax.jit
    def fwd(p, stacked):
        def one(b):
            return mgn.apply(p, cfg, b["node_feats"], b["edge_feats"],
                             b["senders"], b["receivers"],
                             edge_mask=b["edge_mask"])
        return jax.vmap(one)(stacked)

    keys = ("node_feats", "edge_feats", "senders", "receivers", "edge_mask")
    preds = []
    for s, ps in zip(samples, psamples):
        stacked = {k: jnp.asarray(ps.stacked[k]) for k in keys}
        preds_p = np.asarray(fwd(params, stacked))
        pred = np.zeros((s.graph.n_nodes, cfg.node_out), np.float32)
        nodes = np.asarray(ps.padded["nodes_global"])
        owned = np.asarray(ps.padded["owned_mask"]) > 0
        pred[nodes[owned]] = preds_p[owned]
        preds.append(norm_out.decode(pred))
    return preds


def eval_gnn(cfg: GNNConfig, params, samples, norm_in, norm_out) -> dict:
    """Paper Table I metrics on denormalized predictions."""
    errs = {"pressure": [[], []], "tau_x": [[], []], "tau_y": [[], []],
            "tau_z": [[], []]}
    names = list(errs)
    forces_true, forces_pred = [], []
    preds = predict_gnn(cfg, params, samples, norm_in, norm_out)
    for s, pred in zip(samples, preds):
        true = s.targets
        for i, nm in enumerate(names):
            num = np.linalg.norm(pred[:, i] - true[:, i])
            den = np.linalg.norm(true[:, i]) + 1e-12
            errs[nm][0].append(num / den)
            errs[nm][1].append(np.abs(pred[:, i] - true[:, i]).sum()
                               / (np.abs(true[:, i]).sum() + 1e-12))
        n = s.graph.normals
        f_true = ((-true[:, :1] * n + true[:, 1:]).mean(0) @ [1, 0, 0])
        f_pred = ((-pred[:, :1] * n + pred[:, 1:]).mean(0) @ [1, 0, 0])
        forces_true.append(f_true)
        forces_pred.append(f_pred)
    out = {nm: {"rel_l2": float(np.mean(v[0])), "rel_l1": float(np.mean(v[1]))}
           for nm, v in errs.items()}
    ft, fp = np.asarray(forces_true), np.asarray(forces_pred)
    ss_res = np.sum((ft - fp) ** 2)
    ss_tot = np.sum((ft - ft.mean()) ** 2) + 1e-12
    out["force_r2"] = float(1.0 - ss_res / ss_tot)
    return out


def train_llm(arch: str, reduced: bool, steps: int, batch: int = 4,
              seq: int = 64, log_every: int = 5):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    api = registry.get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    opt_cfg = AdamConfig(lr_max=3e-4, total_steps=steps)
    opt = adam_init(params)

    @jax.jit
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(api.train_loss)(params, batch)
        params, opt, m = adam_update(opt_cfg, grads, opt, params)
        return params, opt, loss

    losses = []
    gen = token_batches(cfg.vocab_size, batch, seq, steps)
    extra = {}
    if cfg.frontend == "vision":
        extra["prefix_embeds"] = jnp.zeros((batch, cfg.n_frontend_tokens,
                                            cfg.d_model), jnp.float32)
    if cfg.frontend == "audio":
        extra["audio_embeds"] = jnp.zeros((batch, cfg.n_frontend_tokens,
                                           cfg.d_model), jnp.float32)
    for it, b in enumerate(gen):
        b = {k: jnp.asarray(v) for k, v in b.items()}
        b.update(extra)
        params, opt, loss = step_fn(params, opt, b)
        losses.append(float(loss))
        if it % log_every == 0:
            print(f"step {it:4d} loss {float(loss):.4f}", flush=True)
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--samples", type=int, default=6)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="also write --ckpt every N steps (async, on a "
                    "background thread), not just after the final step")
    ap.add_argument("--keep-ckpts", type=int, default=None,
                    help="retain the K newest periodic checkpoints as "
                    "step-tagged siblings of --ckpt; --resume falls back "
                    "past a corrupt newest file to the previous intact one")
    ap.add_argument("--resume", default=None,
                    help="continue training from this checkpoint: params, "
                    "Adam state, step and LR-schedule horizon are restored "
                    "so the optimizer trajectory matches an uninterrupted "
                    "run exactly")
    ap.add_argument("--total-steps", type=int, default=None,
                    help="cosine-schedule horizon when it differs from "
                    "--steps (a resumed run keeps the checkpoint's horizon "
                    "by default)")
    ap.add_argument("--compile-cache", default=None,
                    help="persistent XLA compilation cache directory "
                    "(ignored when $JAX_COMPILATION_CACHE_DIR is set; "
                    "default <checkout>/.jax_cache): a restarted trainer "
                    "loads its step program from disk instead of "
                    "recompiling")
    ap.add_argument("--graph-source", choices=("host", "graphx"),
                    default=None,
                    help="training-graph build: host cKDTree or the "
                    "device-resident graphx pipeline (mesh-free)")
    ap.add_argument("--shard-devices", type=int, default=None,
                    help="cap partition-parallel width (1 = force the "
                    "single-device scan path)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the span tracer + profiler annotations")
    ap.add_argument("--trace-dir", default=None,
                    help="export trace.jsonl / trace_chrome.json / "
                    "metrics.prom / metrics.json here on exit "
                    "(implies --telemetry)")
    ap.add_argument("--profile", action="store_true",
                    help="additionally capture a full jax.profiler trace "
                    "under <trace-dir>/jax_profile")
    ap.add_argument("--noise-std", type=float, default=None,
                    help="MGN-style training noise: gaussian std added to "
                    "node features each step for rollout stability "
                    "(default: cfg.noise_std, i.e. off)")
    args = ap.parse_args()
    compile_cache.enable(args.compile_cache)
    if args.arch == "xmgn-drivaer":
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        if args.telemetry or args.trace_dir:
            cfg = cfg.replace(telemetry=True, trace_dir=args.trace_dir or "",
                              profile_capture=args.profile)
        tel = Telemetry.from_config(cfg)
        with tel.capture():
            params, losses, (train, test, ni, no) = train_gnn(
                cfg, args.steps, args.samples, args.ckpt,
                graph_source=args.graph_source,
                shard_devices=args.shard_devices, telemetry=tel,
                ckpt_every=args.ckpt_every, resume=args.resume,
                opt_total_steps=args.total_steps,
                keep_ckpts=args.keep_ckpts, noise_std=args.noise_std)
            with tel.span("eval", scope="train", hist=tel.metrics.histogram(
                    "train_stage_eval_seconds",
                    help="wall seconds spent in the 'eval' training stage")):
                metrics = eval_gnn(cfg, params, test, ni, no)
        print(json.dumps(metrics, indent=2))
        if args.trace_dir:
            paths = tel.export()
            print("telemetry artifacts: " +
                  ", ".join(sorted(paths.values())))
    else:
        _, losses = train_llm(args.arch, args.reduced, args.steps)
        print(f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()
