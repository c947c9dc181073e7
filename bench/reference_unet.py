"""Plain reference for served X-UNet3D volume fields, independent of the
program.

It rebuilds each request's cloud from its definition (:func:`sample_cloud`:
the server's seed and the request id) and takes each point's normal from
the triangle it was drawn on; the cell holds the served cloud
(``Result.points``) to it. Features follow the configuration's definition with scipy cKDTree queries
on the host in float64: each voxel's nearest sample within ``sdf_band``
voxels, else its nearest among the first ``sdf_prefix`` samples, gives the
signed distance ``sign((v - q) . n_q) |v - q|`` and its gradient ``sign (v
- q) / |v - q|``, beside the voxel's coordinates and their ``sin pi x``,
``cos pi x`` and ``sin 2 pi x`` per axis. The forward pass is the published
U-Net written out in ``jax.numpy`` and ``lax.conv_general_dilated`` in
float32 under ``jax.default_matmul_precision("highest")``: per level two
3x3x3 convolutions, each after a per-voxel RMS norm and followed by a tanh
GELU, 2x max pooling, nearest 2x upsampling and a 1x1 convolution on the
way up, an attention gate ``skip * sigmoid(psi(relu(wx skip + wg up)))`` on
each skip, and a 1x1 head.

Full-domain output is computed in x-blocks of the reference's own (owned
planes in a window of one width, a halo of at least the receptive field
each side, shifted to lie inside the domain), one block at a time:
:func:`block_fields`.

It also makes the weights, from the seed, in the parameter layout the
program reads. Nothing here imports ``repro``.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from scipy.spatial import cKDTree

from bench.reference import jax_key


# ------------------------------------------------------------------ weights

def channels(cfg: dict) -> List[int]:
    return [cfg["base_channels"] * 2 ** i for i in range(cfg["depth"])]


def conv_shapes(cfg: dict) -> Dict[str, list]:
    """(kernel, c_in, c_out) of every convolution, by part, in the
    program's order: encoder blocks, then per decoder level (deepest first)
    the up conv, the gate's wx, wg and psi, and the block; the head."""
    k, n = cfg["kernel_size"], cfg["blocks_per_level"]
    ch = channels(cfg)
    enc, ups, gates, dec = [], [], [], []
    cin = cfg["in_channels"]
    for c in ch:
        enc.append([(k, cin, c)] + [(k, c, c)] * (n - 1))
        cin = c
    for i in reversed(range(cfg["depth"] - 1)):
        ups.append((1, ch[i + 1], ch[i]))
        inner = max(ch[i] // 2, 1)
        gates.append({"wx": (1, ch[i], inner), "wg": (1, ch[i], inner),
                      "psi": (1, inner, 1)})
        dec.append([(k, 2 * ch[i], ch[i])] + [(k, ch[i], ch[i])] * (n - 1))
    return {"enc": enc, "ups": ups, "gates": gates, "dec": dec,
            "head": (1, ch[0], cfg["out_channels"])}


def init_params(cfg: dict, seed: int):
    """Random float32 weights from ``seed``, made on the device in one
    jitted call: conv weights uniform(+-1/sqrt(k^3 c_in)), biases
    uniform(+-0.1), laid out as the program reads them (``{"enc": [{"convs":
    [{"w", "b"}...]}...], "ups", "gates": [{"wx", "wg", "psi"}...], "dec",
    "head"}``, weights (k, k, k, c_in, c_out))."""
    shapes = conv_shapes(cfg)
    flat = (sum(shapes["enc"], []) + shapes["ups"]
            + [g[n] for g in shapes["gates"] for n in ("wx", "wg", "psi")]
            + sum(shapes["dec"], []) + [shapes["head"]])

    def conv(key, k, cin, cout):
        kw, kb = jax.random.split(key)
        lim = 1.0 / np.sqrt(cin * k ** 3)
        return {"w": jax.random.uniform(kw, (k, k, k, cin, cout),
                                        jnp.float32, -lim, lim),
                "b": jax.random.uniform(kb, (cout,), jnp.float32, -0.1, 0.1)}

    def make(key):
        it = iter(conv(kk, *s) for kk, s in
                  zip(jax.random.split(key, len(flat)), flat))
        enc = [{"convs": [next(it) for _ in blk]} for blk in shapes["enc"]]
        ups = [next(it) for _ in shapes["ups"]]
        gates = [{n: next(it) for n in ("wx", "wg", "psi")}
                 for _ in shapes["gates"]]
        dec = [{"convs": [next(it) for _ in blk]} for blk in shapes["dec"]]
        return {"enc": enc, "ups": ups, "gates": gates, "dec": dec,
                "head": next(it)}

    return jax.jit(make)(jax_key(seed, 7))


# -------------------------------------------------------------------- cloud

def sample_cloud(verts: np.ndarray, faces: np.ndarray, n: int,
                 server_seed: int, request_id: int):
    """The cloud request ``request_id`` of a server seeded ``server_seed``
    is served on, by its definition: ``n`` triangles drawn with
    probability proportional to their area from numpy's
    ``default_rng((server_seed, request_id + 1))``, then a uniform point
    on each (two uniforms, folded into the triangle). Returns (points (n,
    3), unit normals of the drawn triangles (n, 3)), float32."""
    rng = np.random.default_rng((int(server_seed), int(request_id) + 1))
    a, b, c = (verts[faces[:, i]] for i in range(3))
    cross = np.cross(b - a, c - a)
    area = 0.5 * np.linalg.norm(cross, axis=-1)
    tri = rng.choice(len(faces), size=n, p=area / area.sum())
    u = rng.random((n, 1))
    v = rng.random((n, 1))
    flip = (u + v) > 1.0
    u = np.where(flip, 1.0 - u, u)
    v = np.where(flip, 1.0 - v, v)
    a, b, c = a[tri], b[tri], c[tri]
    pts = a + u * (b - a) + v * (c - a)
    nrm = cross / np.maximum(np.linalg.norm(cross, axis=-1, keepdims=True),
                             1e-12)
    return pts.astype(np.float32), nrm[tri].astype(np.float32)


# ----------------------------------------------------------------- features

def voxel_axes(cfg: dict):
    """Voxel-centre coordinates per axis: ``box_lo + (i + 1/2) voxel``,
    rounded to float32 as the program's inputs are."""
    return [(lo + (np.arange(g) + 0.5) * cfg["voxel"]).astype(np.float32)
            for lo, g in zip(cfg["box_lo"], cfg["grid"])]


def features(cfg: dict, pts: np.ndarray, nrm: np.ndarray, x0: int,
             x1: int) -> np.ndarray:
    """The 16 input channels of planes ``[x0, x1)``: (1, x1 - x0, Y, Z, 16)
    float32, computed in float64 (module docstring). The coordinate terms
    of a voxel depend on one axis each and are computed per axis."""
    axes = [a.astype(np.float64) for a in voxel_axes(cfg)]
    axes[0] = axes[0][x0:x1]
    shape = tuple(len(a) for a in axes)
    out = np.empty(shape + (16,), np.float32)
    for i, a in enumerate(axes):
        a = a.reshape([-1 if j == i else 1 for j in range(3)])
        for j, t in enumerate((a, np.sin(np.pi * a), np.cos(np.pi * a),
                               np.sin(2 * np.pi * a))):
            out[..., 3 * j + i] = t
    v = np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(-1, 3)
    p = np.asarray(pts, np.float64)
    # cKDTree's bound is strict: the band holds |v - p| <= sdf_band voxels
    band = np.nextafter(cfg["sdf_band"] * cfg["voxel"], np.inf)
    d, q = cKDTree(p).query(v, distance_upper_bound=band, workers=-1)
    far = ~np.isfinite(d)
    _, q_far = cKDTree(p[:cfg["sdf_prefix"]]).query(v[far], workers=-1)
    q[far] = q_far
    dv = v - p[q]
    dist = np.linalg.norm(dv, axis=1)
    sign = np.sign(np.sum(dv * np.asarray(nrm, np.float64)[q], axis=1))
    grad = dv * np.where(dist > 0, sign / np.where(dist > 0, dist, 1.0),
                         0.0)[:, None]
    out[..., 12] = (sign * dist).reshape(shape)
    out[..., 13:] = grad.reshape(shape + (3,))
    return out[None]


# ------------------------------------------------------------------ forward

def _conv(p, x, prec):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (1, 1, 1), "SAME",
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"), precision=prec)
    return y + p["b"].astype(x.dtype)


def _rms(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + jnp.asarray(eps, x.dtype))


def _block(p, x, prec):
    for c in p["convs"]:
        x = jax.nn.gelu(_conv(c, _rms(x), prec), approximate=True)
    return x


def _pool(x):
    return jax.lax.reduce_window(x, jnp.asarray(-jnp.inf, x.dtype),
                                 jax.lax.max, (1, 2, 2, 2, 1),
                                 (1, 2, 2, 2, 1), "VALID")


def _up(x):
    for axis in (1, 2, 3):
        x = jnp.repeat(x, 2, axis=axis)
    return x


@functools.partial(jax.jit, static_argnames=("depth", "prec"))
def _forward(params, x, depth: int, prec: str):
    skips = []
    for i, bp in enumerate(params["enc"]):
        x = _block(bp, x, prec)
        if i < depth - 1:
            skips.append(x)
            x = _pool(x)
    for j, (up, gp, bp) in enumerate(zip(params["ups"], params["gates"],
                                         params["dec"])):
        x = _conv(up, _up(x), prec)
        skip = skips[-(j + 1)]
        q = jax.nn.relu(_conv(gp["wx"], skip, prec) + _conv(gp["wg"], x, prec))
        skip = skip * jax.nn.sigmoid(_conv(gp["psi"], q, prec))
        x = _block(bp, jnp.concatenate([skip, x], axis=-1), prec)
    return _conv(params["head"], x, prec)


def forward(params, cfg: dict, x: np.ndarray, dtype=jnp.float32):
    """Fields (X', Y, Z, out) of one window of features (1, X', Y, Z, 16),
    in ``dtype`` at the highest matmul precision."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    with jax.default_matmul_precision("highest"):
        y = _forward(p, jnp.asarray(x, dtype), cfg["depth"], "highest")
    return np.asarray(y[0].astype(jnp.float32))


def receptive_field(cfg: dict) -> int:
    """One-sided receptive field in voxels: each 3^3 convolution reaches
    one voxel at its level's stride, on the way down and back up."""
    half = (cfg["kernel_size"] - 1) // 2
    r = 0
    for i in range(cfg["depth"]):
        r += cfg["blocks_per_level"] * half * 2 ** i
    for i in reversed(range(cfg["depth"] - 1)):
        r += cfg["blocks_per_level"] * half * 2 ** (i + 1)
    return r


def block_fields(params, cfg: dict, pts: np.ndarray, nrm: np.ndarray,
                 x0: int, owned: int, halo: int,
                 dtype=jnp.float32, cloud_dtype=None) -> np.ndarray:
    """Full-domain fields of planes ``[x0, x0 + owned)``, (owned, Y, Z,
    out): the reference on a window of ``owned + 2 halo`` planes (the
    whole domain if that is wider) shifted to lie inside the domain, so
    that every block's window has one width (``x0``, ``owned`` and
    ``halo`` multiples of the pooling alignment, ``halo`` at least the
    receptive field). With ``cloud_dtype`` the cloud and normals are first
    rounded to it (the control's featurization)."""
    X = cfg["grid"][0]
    align = 2 ** (cfg["depth"] - 1)
    assert x0 % align == 0 and owned % align == 0 and halo % align == 0
    assert halo >= receptive_field(cfg), (halo, receptive_field(cfg))
    assert 0 <= x0 <= X - owned, (x0, owned, X)
    width = min(owned + 2 * halo, X)
    w0 = min(max(x0 - halo, 0), X - width)
    if cloud_dtype is not None:
        pts = np.asarray(jnp.asarray(pts, cloud_dtype).astype(jnp.float32))
        nrm = np.asarray(jnp.asarray(nrm, cloud_dtype).astype(jnp.float32))
    y = forward(params, cfg, features(cfg, pts, nrm, w0, w0 + width), dtype)
    return y[x0 - w0:x0 - w0 + owned]
