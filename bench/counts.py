"""Operations and bytes the model needs, from a config's widths and the real
(unpadded) nodes and edges of a graph.

Only matrix multiplications count as operations (2 per multiply-add); the
sum aggregation adds one per edge and feature. Bytes are float32 and are the
least any implementation must move through memory per message-passing
layer: the edge state read and written, the node state read and written,
the sender and receiver indices, and the layer's weights.
"""
from __future__ import annotations

from typing import Sequence

from bench.reference import mlp_dims

F32 = 4
I32 = 4


def mlp_flops(dims: Sequence[int]) -> int:
    """Matmul operations of one MLP row."""
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def mlp_weight_bytes(dims: Sequence[int], layernorm: bool) -> int:
    n = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return F32 * (n + (2 * dims[-1] if layernorm else 0))


def mp_flops(cfg: dict, n_nodes: int, n_edges: int) -> int:
    """All message-passing layers: edge MLP per edge, sum aggregation, node
    MLP per node."""
    d = mlp_dims(cfg)
    per_layer = (n_edges * (mlp_flops(d["proc_edge"]) + cfg["hidden"])
                 + n_nodes * mlp_flops(d["proc_node"]))
    return cfg["n_mp_layers"] * per_layer


def mp_bytes(cfg: dict, n_nodes: int, n_edges: int) -> int:
    """Least bytes all message-passing layers must move (module docstring)."""
    d = mlp_dims(cfg)
    h = cfg["hidden"]
    per_layer = (2 * n_edges * h * F32 + 2 * n_nodes * h * F32
                 + 2 * n_edges * I32
                 + mlp_weight_bytes(d["proc_edge"], True)
                 + mlp_weight_bytes(d["proc_node"], True))
    return cfg["n_mp_layers"] * per_layer


def forward_flops(cfg: dict, n_nodes: int, n_edges: int) -> int:
    """One forward pass: encoders, message passing, decoder."""
    d = mlp_dims(cfg)
    return (n_nodes * mlp_flops(d["node_encoder"])
            + n_edges * mlp_flops(d["edge_encoder"])
            + mp_flops(cfg, n_nodes, n_edges)
            + n_nodes * mlp_flops(d["decoder"]))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time: the larger of the compute and the memory
    bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
