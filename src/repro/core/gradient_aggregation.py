"""Gradient aggregation across partitions (paper SIII-A).

Each partition is a self-contained batch; gradients from all partitions are
summed before the optimizer step, making partitioned training *equivalent* to
full-graph training. Two execution modes:

* sequential (single device): python/scan loop accumulating grads — the
  paper's "can even enable training on a single GPU" mode;
* data-parallel (multi device): partitions sharded over the (pod, data) mesh
  axes, aggregation = ``psum`` (see ``repro.core.distributed_mgn``), i.e. DDP.
"""
from __future__ import annotations

from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .halo import Partition


def tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_pvary(tree, axes: tuple):
    """Mark a pytree as device-varying over mesh ``axes`` (inside shard_map).

    Applied to replicated params *before* ``value_and_grad`` so that JAX's
    transpose does NOT auto-insert a per-call psum — we aggregate gradients
    ourselves with exactly one psum per step (the paper's scheme)."""
    axes = tuple(axes)

    def _v(x):
        missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x
    return jax.tree_util.tree_map(_v, tree)


def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def partition_batch(part: Partition, node_feats: np.ndarray,
                    edge_feats: np.ndarray, targets: np.ndarray) -> dict:
    """Gather a partition's local arrays from the full-graph arrays."""
    mask = part.owned_mask().astype(np.float32)
    return {
        "node_feats": node_feats[part.global_nodes],
        "edge_feats": edge_feats[part.edge_ids],
        "senders": part.senders,
        "receivers": part.receivers,
        "targets": targets[part.global_nodes],
        "loss_mask": mask,
    }


def padded_partition_batches(padded: dict, node_feats: np.ndarray,
                             edge_feats: np.ndarray, targets: np.ndarray) -> dict:
    """Stacked (P, ...) batches from ``halo.pad_partitions`` output — the
    static-shape layout used for scan/DDP execution on TPU."""
    return {
        "node_feats": node_feats[padded["nodes_global"]] * padded["node_mask"][..., None],
        "edge_feats": edge_feats[padded["edge_ids"]] * padded["edge_mask"][..., None],
        "senders": padded["senders"],
        "receivers": padded["receivers"],
        "targets": targets[padded["nodes_global"]],
        "loss_mask": padded["owned_mask"],
        "edge_mask": padded["edge_mask"],
    }


def aggregate_gradients(grad_fn: Callable, params, batches: Iterable[dict]):
    """Sequential gradient aggregation: sum of per-partition (loss, grad).

    ``grad_fn(params, batch) -> (loss, grads)`` must compute losses normalized
    by the *global* denominator so the sums reproduce full-graph quantities.
    """
    total_loss = jnp.zeros(())
    total_grads = None
    for b in batches:
        loss, grads = grad_fn(params, b)
        total_loss = total_loss + loss
        total_grads = grads if total_grads is None else tree_add(total_grads, grads)
    return total_loss, total_grads


def scan_aggregate_gradients(grad_fn: Callable, params, stacked_batches: dict,
                             varying_axes: tuple = ()):
    """Same, but as a ``lax.scan`` over the stacked (P, ...) partition batch —
    jit-compiles once regardless of partition count.

    ``varying_axes``: when called inside ``shard_map``, the mesh axes the
    batch varies over (the scan carry must be marked varying to match).
    """
    def body(carry, batch):
        loss_acc, grad_acc = carry
        loss, grads = grad_fn(params, batch)
        return (loss_acc + loss, tree_add(grad_acc, grads)), None

    init = (jnp.zeros(()), tree_zeros_like(params))
    if varying_axes:
        init = tree_pvary(init, tuple(varying_axes))
    (loss, grads), _ = jax.lax.scan(body, init, stacked_batches)
    return loss, grads


def shard_map_aggregate_gradients(mesh, grad_fn: Callable,
                                  axes: Sequence[str] = ("data",),
                                  jit: bool = False):
    """Partition-parallel twin of :func:`scan_aggregate_gradients`.

    Returns ``f(params, stacked_batches) -> (loss, grads)``: ``params`` are
    replicated, the stacked (P, ...) batch is sharded over the mesh ``axes``
    on its leading dim, each device runs the sequential scan over ITS local
    partitions, and the per-device sums are combined with exactly ONE
    ``psum`` per quantity per step — the paper's gradient-aggregation scheme
    (SIII-A) expressed as a collective. P must be divisible by the product
    of the ``axes`` sizes. Equivalence to the single-device scan (and to
    full-graph gradients) is pinned by ``tests/test_train_equivalence.py``.
    """
    from jax.sharding import PartitionSpec as P
    axes = tuple(axes)

    def local(params, stacked):
        # Mark params varying so grads stay LOCAL through the scan; the one
        # psum below is the only cross-device communication of the step.
        params_v = tree_pvary(params, axes)
        loss, grads = scan_aggregate_gradients(grad_fn, params_v, stacked,
                                               varying_axes=axes)
        with jax.named_scope("train/psum"):
            return jax.lax.psum(loss, axes), jax.lax.psum(grads, axes)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axes)),
                       out_specs=(P(), P()))
    return jax.jit(fn) if jit else fn
