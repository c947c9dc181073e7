"""Distributed execution of (X-)MeshGraphNet on a jax device mesh.

Two schemes, mirroring the paper's SIV comparison:

1. **X-MGN partitions-as-DDP** (the paper's contribution): each device owns a
   self-contained partition+halo; the ONLY communication is one gradient
   ``psum`` per step. O(1) collectives per step, independent of the number of
   message-passing layers.

2. **Distributed MeshGraphNet baseline** [17]: the graph is sharded without
   halos; every message-passing layer all-gathers the boundary node features
   so receivers can read remote senders. O(L) collectives per step — the
   communication pattern whose poor strong scaling Fig. 8 demonstrates.

Both are exact (produce full-graph gradients); they differ purely in
communication schedule — which the roofline/strong-scaling benchmarks measure
from the compiled HLO.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.core import halo as halo_lib
from repro.core.gradient_aggregation import (
    padded_partition_batches, scan_aggregate_gradients,
    shard_map_aggregate_gradients, tree_pvary)
from repro.models import meshgraphnet as mgn
from repro.models import nn


# --------------------------------------------------------------------------
# Scheme 1: X-MGN — partitions as DDP batches, one grad psum per step.
# --------------------------------------------------------------------------

def make_xmgn_ddp_grad_fn(mesh, cfg: GNNConfig, denom: Optional[float] = None,
                          data_axes: Sequence[str] = ("data",),
                          jit: bool = True):
    """Returns ``f(params, stacked_batches) -> (loss, grads)`` (jitted by
    default; pass ``jit=False`` to compose it into a larger jitted step, as
    ``launch.train`` does).

    ``stacked_batches`` is the (P, ...) pytree from
    ``gradient_aggregation.padded_partition_batches``; P must be divisible by
    the product of ``data_axes`` sizes. Each device group scans its local
    partitions and the gradients are summed with a single ``psum`` — the
    paper's gradient-aggregation scheme expressed as a JAX collective (the
    shard_map wiring lives in
    ``gradient_aggregation.shard_map_aggregate_gradients``).

    ``denom`` may be baked in as a float, or left ``None``: the loss
    normalizer is then read from the batch's ``"denom"`` leaf — a (P,)
    array repeating the per-sample global denominator — so one compiled
    step serves samples of different sizes (the trainer's case).
    """
    def grad_fn(p, b):
        d = b["denom"] if denom is None else denom
        return jax.value_and_grad(
            lambda q: mgn.loss_fn(q, cfg, b, denom=d))(p)

    return shard_map_aggregate_gradients(mesh, grad_fn,
                                         axes=tuple(data_axes), jit=jit)


# --------------------------------------------------------------------------
# Scheme 2: Distributed MeshGraphNet baseline — per-layer boundary exchange.
# --------------------------------------------------------------------------

def prepare_dmgn_shards(senders: np.ndarray, receivers: np.ndarray,
                        labels: np.ndarray, n_dev: int,
                        node_feats: np.ndarray, edge_feats: np.ndarray,
                        targets: np.ndarray) -> dict:
    """Shard a graph for distributed message passing (NO halo).

    Device d owns nodes with ``labels == d`` and all edges whose receiver it
    owns. Senders living on other devices are read from a per-layer
    all-gathered *boundary buffer*: every device contributes its owned nodes
    that send across a partition boundary, padded to the max count B.

    Edge sender indexing uses a concatenated table: local slot i for i < Nmax,
    else (Nmax + dev*B + pos) into the gathered boundary buffer.
    """
    n_nodes = labels.shape[0]
    cross = labels[senders] != labels[receivers]
    boundary_nodes = [np.unique(senders[cross & (labels[senders] == d)])
                      for d in range(n_dev)]
    B = max((len(b) for b in boundary_nodes), default=1) or 1
    Nmax = int(np.bincount(labels, minlength=n_dev).max())
    Emax = int(np.bincount(labels[receivers], minlength=n_dev).max())

    # global node -> (device, local slot) and -> boundary slot
    local_of = np.full(n_nodes, -1, np.int64)
    for d in range(n_dev):
        own = np.where(labels == d)[0]
        local_of[own] = np.arange(len(own))
    bslot_of = np.full(n_nodes, -1, np.int64)
    for d, b in enumerate(boundary_nodes):
        bslot_of[b] = d * B + np.arange(len(b))

    out = {
        "node_feats": np.zeros((n_dev, Nmax, node_feats.shape[1]), np.float32),
        "targets": np.zeros((n_dev, Nmax, targets.shape[1]), np.float32),
        "node_mask": np.zeros((n_dev, Nmax), np.float32),
        "edge_feats": np.zeros((n_dev, Emax, edge_feats.shape[1]), np.float32),
        "edge_mask": np.zeros((n_dev, Emax), np.float32),
        "senders_slot": np.zeros((n_dev, Emax), np.int32),   # [0, Nmax + n_dev*B)
        "receivers": np.zeros((n_dev, Emax), np.int32),
        "boundary_gather": np.zeros((n_dev, B), np.int32),   # local ids to export
        "boundary_mask": np.zeros((n_dev, B), np.float32),
    }
    for d in range(n_dev):
        own = np.where(labels == d)[0]
        out["node_feats"][d, : len(own)] = node_feats[own]
        out["targets"][d, : len(own)] = targets[own]
        out["node_mask"][d, : len(own)] = 1.0
        eid = np.where(labels[receivers] == d)[0]
        out["edge_feats"][d, : len(eid)] = edge_feats[eid]
        out["edge_mask"][d, : len(eid)] = 1.0
        out["receivers"][d, : len(eid)] = local_of[receivers[eid]]
        es = senders[eid]
        is_local = labels[es] == d
        slot = np.where(is_local, local_of[es], Nmax + bslot_of[es])
        out["senders_slot"][d, : len(eid)] = slot
        b = boundary_nodes[d]
        out["boundary_gather"][d, : len(b)] = local_of[b]
        out["boundary_mask"][d, : len(b)] = 1.0
    out["meta"] = {"B": B, "Nmax": Nmax, "Emax": Emax, "n_dev": n_dev}
    return out


def dmgn_apply_local(params, cfg: GNNConfig, shard: dict, axis: str = "data"):
    """Distributed-MGN forward on one device's shard; runs inside shard_map.

    Per message-passing layer: all_gather boundary node features, compute
    messages with (local | gathered) sender features, aggregate locally.
    """
    nf = shard["node_feats"]
    ef = shard["edge_feats"]
    senders_slot = shard["senders_slot"]
    receivers = shard["receivers"]
    edge_mask = shard["edge_mask"]
    node_mask = shard["node_mask"]
    n_local = nf.shape[0]
    act = cfg.act

    h = nn.mlp(params["node_encoder"], nf, act) * node_mask[:, None]
    e = nn.mlp(params["edge_encoder"], ef, act) * edge_mask[:, None]

    def exchange(h):
        # export this device's boundary rows, all_gather across the mesh axis
        exported = h[shard["boundary_gather"]] * shard["boundary_mask"][:, None]
        gathered = jax.lax.all_gather(exported, axis)          # (n_dev, B, H)
        return gathered.reshape(-1, h.shape[-1])               # (n_dev*B, H)

    def mp_layer(carry, layer_params):
        h, e = carry
        pe, pn = layer_params
        table = jnp.concatenate([h, exchange(h)], axis=0)      # THE per-layer collective
        h_send = table[senders_slot]
        h_recv = h[receivers]
        e_new = e + nn.mlp(pe, jnp.concatenate([h_send, h_recv, e], -1), act)
        e_new = e_new * edge_mask[:, None]
        agg = jax.ops.segment_sum(e_new, receivers, num_segments=n_local)
        h_new = h + nn.mlp(pn, jnp.concatenate([h, agg], -1), act)
        h_new = h_new * node_mask[:, None]
        return (h_new, e_new), None

    (h, e), _ = jax.lax.scan(mp_layer, (h, e),
                             (params["proc_edge"], params["proc_node"]))
    return nn.mlp(params["decoder"], h, act)


def make_dmgn_grad_fn(mesh, cfg: GNNConfig, denom: float, axis: str = "data"):
    """Jitted distributed-MGN loss+grad over the mesh's data axis."""

    def local(params, shard):
        # each device owns exactly one graph shard: strip the sharded axis
        shard = jax.tree_util.tree_map(lambda x: x[0], shard)
        params_v = tree_pvary(params, (axis,))

        def loss(p):
            pred = dmgn_apply_local(p, cfg, shard, axis)
            se = jnp.sum(jnp.square(pred - shard["targets"])
                         * shard["node_mask"][:, None])
            return se / denom
        l, g = jax.value_and_grad(loss)(params_v)
        return jax.lax.psum(l, axis), jax.lax.psum(g, axis)

    shard_spec = {k: P(axis) for k in
                  ("node_feats", "targets", "node_mask", "edge_feats",
                   "edge_mask", "senders_slot", "receivers",
                   "boundary_gather", "boundary_mask")}
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(), shard_spec),
                       out_specs=(P(), P()))
    return jax.jit(fn)


def device_put_shards(shards: dict, mesh, axis: str = "data") -> dict:
    arrays = {k: v for k, v in shards.items() if k != "meta"}
    return {k: jax.device_put(jnp.asarray(v),
                              NamedSharding(mesh, P(axis)))
            for k, v in arrays.items()}
