"""Plain reference for served surface fields, independent of the program.

It starts from the cloud the server served and the geometry it was drawn
from: each point's normal is that of the triangle it lies on, then a scipy
cKDTree k-nearest-neighbour graph on every nested level with the union over
levels, numpy node and edge features, and a straightforward ``jax.numpy``
forward pass (encoder, message passing with sum aggregation, decoder) in
float32 at the matmul precision the configuration states
(``matmul_precision``).

It also makes the weights, from the seed, in the parameter layout the
program reads, so that the program and the reference share the benchmark's
weights and nothing the program made. Nothing here imports ``repro``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from scipy.spatial import cKDTree

ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
EDGE_BLOCK = 1 << 16          # edges per block of the edge update


# ------------------------------------------------------------------ weights

def mlp_dims(cfg: dict) -> Dict[str, List[int]]:
    """Layer widths of each MLP: encoders, processor, decoder."""
    h = cfg["hidden"]
    hid = [h] * cfg["mlp_layers"]
    return {"node_encoder": [cfg["node_in"]] + hid + [h],
            "edge_encoder": [cfg["edge_in"]] + hid + [h],
            "proc_edge": [3 * h] + hid + [h],
            "proc_node": [2 * h] + hid + [h],
            "decoder": [h] + hid + [cfg["node_out"]]}


def jax_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def _mlp_init(key, dims, stack: int, layernorm: bool, ln_scale=1.0):
    lead = (stack,) if stack else ()
    keys = jax.random.split(key, 2 * len(dims))
    layers = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        lim = 1.0 / np.sqrt(a)
        layers.append({
            "w": jax.random.uniform(keys[2 * i], lead + (a, b), jnp.float32,
                                    -lim, lim),
            "b": jax.random.uniform(keys[2 * i + 1], lead + (b,),
                                    jnp.float32, -0.1, 0.1)})
    p = {"layers": layers}
    if layernorm:
        ks, kb = jax.random.split(keys[-1])
        p["ln"] = {
            "scale": ln_scale * jax.random.uniform(
                ks, lead + (dims[-1],), jnp.float32, 0.9, 1.1),
            "bias": ln_scale * jax.random.uniform(
                kb, lead + (dims[-1],), jnp.float32, -0.1, 0.1)}
    return p


def init_params(cfg: dict, seed: int):
    """Random float32 weights from ``seed``, made on the device in one jitted
    call, in the program's layout: an MLP is ``{"layers": [{"w", "b"}...],
    "ln": {"scale", "bias"}}``; the processor's layers are stacked on a
    leading axis of length ``n_mp_layers``. The processor's LayerNorm
    scale and bias are multiplied by the config's ``processor_ln_scale``
    (default 1), which sets each layer's residual update against the
    latent it adds to."""
    dims = mlp_dims(cfg)
    n_mp = cfg["n_mp_layers"]
    ln = cfg.get("processor_ln_scale", 1.0)

    def make(key):
        k = jax.random.split(key, 5)
        return {"node_encoder": _mlp_init(k[0], dims["node_encoder"], 0, True),
                "edge_encoder": _mlp_init(k[1], dims["edge_encoder"], 0, True),
                "proc_edge": _mlp_init(k[2], dims["proc_edge"], n_mp, True,
                                       ln),
                "proc_node": _mlp_init(k[3], dims["proc_node"], n_mp, True,
                                       ln),
                "decoder": _mlp_init(k[4], dims["decoder"], 0, False)}

    return jax.jit(make)(jax_key(seed))


# ------------------------------------------------------------- point clouds

def level_sizes(n_points: int, n_levels: int) -> Tuple[int, ...]:
    """Nested prefix sizes n/2^(L-1), ..., n/2, n."""
    return tuple(n_points // 2 ** (n_levels - 1 - i) for i in range(n_levels))


def _outside(p, a, b, c):
    """How far each point lies from each triangle: its distance from the
    triangle's plane plus, where its barycentric coordinates leave the
    triangle, that excess scaled by the triangle's size. Broadcasts."""
    e0, e1, d = b - a, c - a, p - a
    d00, d01, d11 = (np.sum(x * y, -1) for x, y in ((e0, e0), (e0, e1),
                                                    (e1, e1)))
    d20, d21 = np.sum(d * e0, -1), np.sum(d * e1, -1)
    den = np.maximum(d00 * d11 - d01 * d01, 1e-300)
    v = (d11 * d20 - d01 * d21) / den
    w = (d00 * d21 - d01 * d20) / den
    bary = np.minimum(np.minimum(v, w), 1.0 - v - w)
    cross = np.cross(e0, e1)
    area2 = np.linalg.norm(cross, axis=-1)
    plane = np.abs(np.sum(d * cross, -1)) / np.maximum(area2, 1e-300)
    return plane + np.maximum(-bary, 0.0) * np.sqrt(area2)


def point_normals(verts: np.ndarray, faces: np.ndarray, pts: np.ndarray,
                  k: Sequence[int] = (64, 1024)) -> Tuple[np.ndarray, int]:
    """The unit normal of the triangle each point lies on, for a cloud drawn
    from the surface (float32, as the vertices are). Candidates are the
    ``k[0]`` triangles with the nearest centroids, and the one the point lies
    most inside wins; a point that none holds to within the rounding of its
    float32 coordinates tries ``k[1]``, then every triangle (slivers near a
    pole have far centroids). Returns (normals (n, 3) float32, the number of
    points that needed more than ``k[0]``)."""
    cross32 = np.cross(verts[faces[:, 1]] - verts[faces[:, 0]],
                       verts[faces[:, 2]] - verts[faces[:, 0]])
    nrm = cross32 / np.maximum(
        np.linalg.norm(cross32, axis=-1, keepdims=True), 1e-12)
    v = verts.astype(np.float64)
    a, b, c = (v[faces[:, i]] for i in range(3))
    tol = 4 * np.finfo(np.float32).eps * np.abs(v).max()
    tree = cKDTree((a + b + c) / 3.0)
    p = pts.astype(np.float64)
    best = np.zeros(len(p), np.int64)
    todo = np.arange(len(p))
    n_far = 0
    for j, kq in enumerate(k):
        kq = min(kq, len(faces))
        _, cand = tree.query(p[todo], k=kq)
        cand = cand.reshape(len(todo), kq)
        score = _outside(p[todo, None, :], a[cand], b[cand], c[cand])
        best[todo] = cand[np.arange(len(todo)), np.argmin(score, axis=1)]
        todo = todo[score.min(axis=1) > tol]
        if j == 0:
            n_far = len(todo)
    for i in todo:
        best[i] = int(np.argmin(_outside(p[i], a, b, c)))
    return nrm[best].astype(np.float32), n_far


# -------------------------------------------------------------------- graph

def knn_pairs(pts: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Each point's k nearest other points: (senders, receivers), one edge
    neighbour -> point per neighbour."""
    n = len(pts)
    kq = min(k + 1, n)
    _, idx = cKDTree(pts).query(pts, k=kq)
    idx = idx.reshape(n, kq)
    recv = np.repeat(np.arange(n), kq)
    send = idx.reshape(-1)
    keep = send != recv
    send, recv = send[keep], recv[keep]
    # a point may have other points at distance 0 ahead of itself: keep the
    # first k non-self neighbours of each receiver, in distance order
    rank = np.arange(len(recv)) - np.searchsorted(recv, recv, side="left")
    keep = rank < k
    return send[keep], recv[keep]


def multiscale_graph(pts: np.ndarray, levels: Sequence[int], k: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Union over nested levels of the symmetric kNN graph of each level's
    prefix of the cloud; every directed edge once. Returns (senders,
    receivers), int32."""
    n = len(pts)
    keys = []
    for m in levels:
        s, r = knn_pairs(pts[:m], k)
        keys += [s.astype(np.int64) * n + r, r.astype(np.int64) * n + s]
    key = np.unique(np.concatenate(keys))
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def node_features(pts: np.ndarray, nrm: np.ndarray, freqs) -> np.ndarray:
    """Position, normal, then sin and cos of pi*f*position for each f."""
    x = pts.astype(np.float64)
    parts = [x, nrm.astype(np.float64)]
    for f in freqs:
        parts += [np.sin(np.pi * f * x), np.cos(np.pi * f * x)]
    return np.concatenate(parts, axis=-1).astype(np.float32)


def edge_features(pts: np.ndarray, s: np.ndarray, r: np.ndarray):
    """Sender minus receiver position, and its length."""
    rel = pts[s].astype(np.float64) - pts[r].astype(np.float64)
    dist = np.linalg.norm(rel, axis=-1, keepdims=True)
    return np.concatenate([rel, dist], axis=-1).astype(np.float32)


# ------------------------------------------------------------------ forward

def _layernorm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mlp(p, x, act: str, prec: str):
    n = len(p["layers"])
    for i, lp in enumerate(p["layers"]):
        x = jnp.dot(x, lp["w"], precision=prec) + lp["b"]
        if i < n - 1:
            x = ACTS[act](x)
    if "ln" in p:
        x = _layernorm(p["ln"], x)
    return x


@functools.partial(jax.jit, static_argnames=("act", "prec"))
def _encode(pn, pe, nf, ef, act, prec):
    return _mlp(pn, nf, act, prec), _mlp(pe, ef, act, prec)


@functools.partial(jax.jit, static_argnames=("act", "prec"))
def _mp_layer(pe, pn, h, e, s, r, act, prec):
    """One message-passing layer; edges in blocks of EDGE_BLOCK (padding
    edges carry receiver n, outside the node range, and are dropped)."""
    n = h.shape[0]
    nb = e.shape[0] // EDGE_BLOCK

    def edge_block(args):
        eb, sb, rb = args
        msg = jnp.concatenate([h[sb], h[jnp.minimum(rb, n - 1)], eb], -1)
        return eb + _mlp(pe, msg, act, prec)

    blocks = (e.reshape(nb, EDGE_BLOCK, -1), s.reshape(nb, EDGE_BLOCK),
              r.reshape(nb, EDGE_BLOCK))
    e_new = jax.lax.map(edge_block, blocks).reshape(e.shape)
    agg = jax.ops.segment_sum(e_new, r, num_segments=n + 1)[:n]
    h_new = h + _mlp(pn, jnp.concatenate([h, agg], -1), act, prec)
    return h_new, e_new


@functools.partial(jax.jit, static_argnames=("act", "prec"))
def _decode(pd, h, act, prec):
    return _mlp(pd, h, act, prec)


def forward(params, cfg: dict, nf: np.ndarray, ef: np.ndarray,
            s: np.ndarray, r: np.ndarray, dtype=jnp.float32) -> np.ndarray:
    """Fields (n, node_out) of one graph, computed in ``dtype`` at the
    config's ``matmul_precision`` (``default``, ``high`` or ``highest``),
    one jitted call per layer."""
    n, e = len(nf), len(s)
    pad = -e % EDGE_BLOCK
    s = np.concatenate([s, np.zeros(pad, np.int32)])
    r = np.concatenate([r, np.full(pad, n, np.int32)])
    ef = np.concatenate([ef, np.zeros((pad, ef.shape[1]), np.float32)])
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    act, prec = cfg["act"], cfg["matmul_precision"]
    with jax.default_matmul_precision(prec):
        h, e_ = _encode(p["node_encoder"], p["edge_encoder"],
                        jnp.asarray(nf, dtype), jnp.asarray(ef, dtype), act,
                        prec)
        s_d, r_d = jnp.asarray(s), jnp.asarray(r)
        for layer in range(cfg["n_mp_layers"]):
            pe = jax.tree_util.tree_map(lambda a: a[layer], p["proc_edge"])
            pn = jax.tree_util.tree_map(lambda a: a[layer], p["proc_node"])
            h, e_ = _mp_layer(pe, pn, h, e_, s_d, r_d, act, prec)
        out = _decode(p["decoder"], h, act, prec)
    return np.asarray(out.astype(jnp.float32))


def graph_inputs(cfg: dict, pts: np.ndarray, nrm: np.ndarray):
    """(node feats, edge feats, senders, receivers) of one served cloud."""
    levels = level_sizes(len(pts), cfg["n_levels"])
    s, r = multiscale_graph(pts, levels, cfg["k_neighbors"])
    return (node_features(pts, nrm, cfg["fourier_freqs"]),
            edge_features(pts, s, r), s, r)


def low_precision_inputs(cfg: dict, pts: np.ndarray, nrm: np.ndarray,
                         s: np.ndarray, r: np.ndarray, dtype):
    """Node and edge features computed in ``dtype`` from the cloud rounded
    to ``dtype``: the featurization of a reference run wholly in that
    precision (the control)."""
    p = jnp.asarray(pts, dtype)
    parts = [p, jnp.asarray(nrm, dtype)]
    for f in cfg["fourier_freqs"]:
        parts += [jnp.sin(jnp.asarray(np.pi * f, dtype) * p),
                  jnp.cos(jnp.asarray(np.pi * f, dtype) * p)]
    rel = p[s] - p[r]
    ef = jnp.concatenate([rel, jnp.sqrt(jnp.sum(rel * rel, -1,
                                                keepdims=True))], -1)
    as32 = lambda a: np.asarray(a.astype(jnp.float32))
    return as32(jnp.concatenate(parts, -1)), as32(ef)


def rms_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Root mean square of ``got - want`` over every value, in float64."""
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt(np.mean(np.square(d))))
