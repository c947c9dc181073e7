"""MeshGraphNet / X-MeshGraphNet model (paper SII + SIII-D).

Encoder -> L message-passing processor layers (distinct params, residual edge
and node updates, MLPs with trailing LayerNorm) -> decoder. All normalization
is feature-local (LayerNorm) — batch statistics would break the partition
equivalence (paper SIII-A) and are deliberately unsupported.

The processor aggregation (scatter-add of messages) has three jittable
implementations, selected by ``cfg.agg_impl`` (or the ``agg_impl`` argument):
``'xla'`` is plain ``jax.ops.segment_sum``; ``'sorted'`` argsorts edges by
receiver once per graph and reduces with ``indices_are_sorted=True``;
``'pallas'`` packs the sorted edges into fixed node blocks and runs the TPU
kernel in ``repro.kernels.segment_agg`` (scatter-as-one-hot-MXU-matmul).
The per-graph sort/packing happens once, outside the layer scan.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import GNNConfig
from repro.models import nn


def init(key, cfg: GNNConfig, dtype=jnp.float32):
    k_ne, k_ee, k_pe, k_pn, k_d = jax.random.split(key, 5)
    h = cfg.hidden
    hidden_dims = [h] * cfg.mlp_layers

    def edge_layer_init(k):
        return nn.mlp_init(k, [3 * h] + hidden_dims + [h], dtype, final_layernorm=True)

    def node_layer_init(k):
        return nn.mlp_init(k, [2 * h] + hidden_dims + [h], dtype, final_layernorm=True)

    return {
        "node_encoder": nn.mlp_init(k_ne, [cfg.node_in_eff] + hidden_dims + [h], dtype, final_layernorm=True),
        "edge_encoder": nn.mlp_init(k_ee, [cfg.edge_in] + hidden_dims + [h], dtype, final_layernorm=True),
        "proc_edge": nn.stacked_init(k_pe, cfg.n_mp_layers, edge_layer_init),
        "proc_node": nn.stacked_init(k_pn, cfg.n_mp_layers, node_layer_init),
        "decoder": nn.mlp_init(k_d, [h] + hidden_dims + [cfg.node_out], dtype),
    }


def make_aggregator(receivers, n_nodes: int, agg_impl: str):
    """Build ``agg(messages) -> (n_nodes, D)`` once per graph.

    The per-graph preprocessing (device argsort for ``'sorted'``, sorted
    block packing for ``'pallas'``) happens HERE, outside the layer scan, so
    its cost amortizes over every message-passing step. All three impls are
    fully jittable — ``receivers`` may be a tracer.

    ``'pallas'`` packs edges into a static per-node-block budget
    (``default_eblk``); if a pathological graph overflows it, a ``lax.cond``
    falls back to the plain scatter-add, so the result is always exact.
    Note the cond is on traced data: under ``vmap`` it lowers to a select
    that executes BOTH branches — the pallas path is meant for the
    unbatched pipelines (per-shard ``shard_map`` serving, training), where
    it stays a true branch. Callers with masked edge buffers should spread
    the padding edges' segment ids (see ``apply``) so the budget holds and
    the fallback stays cold.
    """
    if agg_impl == "sorted":
        from repro.kernels.segment_agg import ops as segops
        order, sorted_ids = segops.sort_by_segment(receivers)
        return lambda msgs: segops.segment_sum_sorted(
            msgs, order, sorted_ids, n_nodes)
    if agg_impl == "pallas":
        from repro.kernels.segment_agg import ops as segops
        prep = segops.prepare_device(receivers, n_nodes)

        def agg(msgs):
            return jax.lax.cond(
                prep.n_dropped > 0,
                lambda m: jax.ops.segment_sum(m, receivers,
                                              num_segments=n_nodes),
                lambda m: segops.segment_sum_prepared(prep, m),
                msgs)
        return agg
    if agg_impl != "xla":
        raise ValueError(f"unknown agg_impl {agg_impl!r} "
                         "(expected 'xla' | 'sorted' | 'pallas')")
    return lambda msgs: jax.ops.segment_sum(msgs, receivers,
                                            num_segments=n_nodes)


def spread_padding(receivers, edge_mask, n_nodes: int):
    """Receiver ids the Pallas aggregation packs by.

    Padding edge slots all carry receiver 0 (the fixed-shape edge union's
    convention), which would pile every masked slot into node block 0 and
    overflow the static EBLK budget at real bucket sizes. Their messages
    are zeroed before aggregation, so scatter them uniformly across
    segments instead — zero contribution anywhere, and the packing budget
    sees balanced load.
    """
    spread = jnp.arange(receivers.shape[0], dtype=receivers.dtype) % n_nodes
    return jnp.where(edge_mask.astype(bool), receivers, spread)


def apply(params, cfg: GNNConfig, node_feats, edge_feats, senders, receivers,
          edge_mask: Optional[jnp.ndarray] = None,
          agg_impl: Optional[str] = None):
    """Forward pass on one (sub)graph.

    node_feats: (N, node_in); edge_feats: (E, edge_in);
    senders/receivers: (E,) int32; edge_mask: (E,) 1.0 for real edges.
    ``agg_impl`` overrides ``cfg.agg_impl`` (None -> use the config).
    Returns (N, node_out).
    """
    n_nodes = node_feats.shape[0]
    act = cfg.act
    impl = agg_impl or cfg.agg_impl
    agg_receivers = receivers
    if impl == "pallas" and edge_mask is not None:
        agg_receivers = spread_padding(receivers, edge_mask, n_nodes)
    aggregate = make_aggregator(agg_receivers, n_nodes, impl)
    # named scopes label the HLO ops by model stage in jax.profiler captures
    with jax.named_scope("mgn/encode"):
        h = nn.mlp(params["node_encoder"], node_feats, act)
        e = nn.mlp(params["edge_encoder"], edge_feats, act)
        if edge_mask is not None:
            e = e * edge_mask[:, None]

    def mp_layer(carry, layer_params):
        h, e = carry
        pe, pn = layer_params
        with jax.named_scope("mgn/message_passing"):
            msg_in = jnp.concatenate([h[senders], h[receivers], e], axis=-1)
            e_new = e + nn.mlp(pe, msg_in, act)
            if edge_mask is not None:
                e_new = e_new * edge_mask[:, None]
            with jax.named_scope("mgn/aggregate"):
                agg = aggregate(e_new)
            h_new = h + nn.mlp(pn, jnp.concatenate([h, agg], axis=-1), act)
        return (h_new, e_new), None

    if getattr(cfg, "remat", True):
        # activation checkpointing (paper SV-D): save only the per-layer
        # (h, e) carries; recompute MLP intermediates in the backward pass
        mp_layer = jax.checkpoint(
            mp_layer, policy=jax.checkpoint_policies.nothing_saveable)
    (h, e), _ = jax.lax.scan(mp_layer, (h, e), (params["proc_edge"], params["proc_node"]))
    with jax.named_scope("mgn/decode"):
        return nn.mlp(params["decoder"], h, act)


def step(params, cfg: GNNConfig, node_feats, edge_feats, senders, receivers,
         state, *, edge_mask: Optional[jnp.ndarray] = None, out_stats=None,
         agg_impl: Optional[str] = None):
    """One autoregressive physics step: state (N, node_out) -> state'.

    The reusable core of both single-shot serving (T=1 from a zero state
    with the ``'direct'`` integrator — identical math to a plain forward)
    and the rollout engine's ``lax.scan`` generate loop.

    With ``cfg.rollout_state_feats`` the current state — normalized by
    ``out_stats`` so it lives in the same space as the targets the decoder
    was trained against — is appended to the static node features before
    the encoder (the encoder must have been initialized with
    ``cfg.node_in_eff`` inputs). ``out_stats`` is an optional
    ``(mean, std)`` pair for the output space; the raw model prediction is
    denormalized by it before integration.
    """
    feats = node_feats
    if cfg.rollout_state_feats:
        s = state
        if out_stats is not None:
            out_mu, out_sd = out_stats
            s = (state - out_mu) / out_sd
        feats = jnp.concatenate([feats, s.astype(feats.dtype)], axis=-1)
    pred = apply(params, cfg, feats, edge_feats, senders, receivers,
                 edge_mask=edge_mask, agg_impl=agg_impl)
    if out_stats is not None:
        out_mu, out_sd = out_stats
        pred = pred * out_sd + out_mu
    if cfg.rollout_integrator == "residual":
        return state + pred
    if cfg.rollout_integrator != "direct":
        raise ValueError(f"unknown rollout_integrator {cfg.rollout_integrator!r} "
                         "(expected 'direct' | 'residual')")
    return pred


def masked_mse(pred, target, mask, denom=None):
    """Sum of squared errors over masked nodes, divided by ``denom``.

    With ``denom = total_owned_nodes * node_out`` summed across partitions,
    partition losses add up exactly to the full-graph mean-squared error —
    the normalization required for gradient-aggregation equivalence
    (paper SIII-A: halo nodes are filtered out before the loss).
    """
    se = jnp.sum(jnp.square(pred - target) * mask[:, None])
    if denom is None:
        denom = jnp.maximum(jnp.sum(mask) * pred.shape[-1], 1.0)
    return se / denom


def loss_fn(params, cfg: GNNConfig, batch, denom=None,
            agg_impl: Optional[str] = None):
    """batch keys: node_feats, edge_feats, senders, receivers, targets,
    loss_mask (owned nodes), optional edge_mask."""
    pred = apply(params, cfg, batch["node_feats"], batch["edge_feats"],
                 batch["senders"], batch["receivers"],
                 edge_mask=batch.get("edge_mask"), agg_impl=agg_impl)
    return masked_mse(pred, batch["targets"], batch["loss_mask"], denom)
