"""X-UNet3D (paper SVI): 3D UNet with attention gates, built so that halo
partitioning is EXACT: every operation is either pointwise, a finite-support
convolution, or pooling/upsampling aligned to the partition grid. No
spatial-statistics normalization (that would couple distant voxels and break
the halo equivalence) — normalization is per-voxel RMS over channels.

Layout: (B, X, Y, Z, C). Pool size 2 per level; partition offsets must be
multiples of 2**(depth-1) so pooling windows align across partitions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import UNetConfig
from repro.models import nn


def conv_init(key, k, cin, cout, dtype=jnp.float32):
    lim = (1.0 / (cin * k ** 3)) ** 0.5
    w = jax.random.uniform(key, (k, k, k, cin, cout), jnp.float32, -lim, lim)
    return {"w": w.astype(dtype), "b": jnp.zeros((cout,), dtype)}


def conv3d(p, x, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, p["w"], (stride,) * 3, padding,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC")) + p["b"]


def voxel_rms(x, eps=1e-6):
    """Per-voxel RMS norm over channels — strictly local."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def block_init(key, k, cin, cout, n_convs, dtype=jnp.float32):
    ks = jax.random.split(key, n_convs)
    convs = []
    c = cin
    for i in range(n_convs):
        convs.append(conv_init(ks[i], k, c, cout, dtype))
        c = cout
    return {"convs": convs}


def block_apply(p, x, act):
    a = nn.ACTS[act]
    for cp in p["convs"]:
        x = a(conv3d(cp, voxel_rms(x)))
    return x


def gate_init(key, c_skip, c_gate, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    ci = max(c_skip // 2, 1)
    return {
        "wx": conv_init(k1, 1, c_skip, ci, dtype),
        "wg": conv_init(k2, 1, c_gate, ci, dtype),
        "psi": conv_init(k3, 1, ci, 1, dtype),
    }


def gate_apply(p, skip, coarse):
    """Attention gate (1x1 convs — local):
    skip * sigmoid(psi(relu(wx*skip + wg*g))) with ``g`` the nearest 2x
    upsample of ``coarse``. A 1x1 conv commutes with nearest upsampling, so
    ``wg*g`` is computed at the coarse level and upsampled; ``psi`` (one
    output channel) is a sum over channels, which leaves no 1-channel
    tensor for a TPU to pad to 128 lanes."""
    q = jax.nn.relu(conv3d(p["wx"], skip)
                    + _upsample(conv3d(p["wg"], coarse)))
    psi = jnp.einsum("bxyzc,c->bxyz", q, p["psi"]["w"][0, 0, 0, :, 0])
    return skip * jax.nn.sigmoid(psi + p["psi"]["b"][0])[..., None]


def init(key, cfg: UNetConfig, dtype=jnp.float32):
    keys = jax.random.split(key, 4 * cfg.depth + 2)
    ch = [cfg.base_channels * (2 ** i) for i in range(cfg.depth)]
    enc, dec, gates, ups = [], [], [], []
    cin = cfg.in_channels
    ki = 0
    for i in range(cfg.depth):
        enc.append(block_init(keys[ki], cfg.kernel_size, cin, ch[i],
                              cfg.blocks_per_level, dtype)); ki += 1
        cin = ch[i]
    for i in reversed(range(cfg.depth - 1)):
        ups.append(conv_init(keys[ki], 1, ch[i + 1], ch[i], dtype)); ki += 1
        if cfg.attention_gates:
            gates.append(gate_init(keys[ki], ch[i], ch[i], dtype)); ki += 1
        else:
            gates.append(None)
        dec.append(block_init(keys[ki], cfg.kernel_size, 2 * ch[i], ch[i],
                              cfg.blocks_per_level, dtype)); ki += 1
    return {
        "enc": enc, "dec": dec, "gates": gates, "ups": ups,
        "head": conv_init(keys[ki], 1, ch[0], cfg.out_channels, dtype),
    }


def _pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 2, 1), (1, 2, 2, 2, 1), "VALID")


def _upsample(x):
    b, d, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :, None, :],
                         (b, d, 2, h, 2, w, 2, c))
    return x.reshape(b, 2 * d, 2 * h, 2 * w, c)


def apply(params, cfg: UNetConfig, x):
    """x: (B, X, Y, Z, in_channels) -> (B, X, Y, Z, out_channels).
    Spatial dims must be divisible by 2**(depth-1). Named scopes
    ``unet/encode``, ``unet/gate``, ``unet/decode`` and ``unet/head`` label
    the compiled ops by stage."""
    act = cfg.act
    skips = []
    with jax.named_scope("unet/encode"):
        for i, bp in enumerate(params["enc"]):
            x = block_apply(bp, x, act)
            if i < cfg.depth - 1:
                skips.append(x)
                x = _pool(x)
    for j, (up, gp, bp) in enumerate(zip(params["ups"], params["gates"],
                                         params["dec"])):
        # nearest upsample then 1x1 conv, computed in the other (equal)
        # order: the conv runs on the coarse level's voxels
        with jax.named_scope("unet/decode"):
            u = conv3d(up, x)
        skip = skips[-(j + 1)]
        if gp is not None:
            with jax.named_scope("unet/gate"):
                skip = gate_apply(gp, skip, u)
        with jax.named_scope("unet/decode"):
            x = block_apply(bp, jnp.concatenate([skip, _upsample(u)], -1),
                            act)
    with jax.named_scope("unet/head"):
        return conv3d(params["head"], x)


def receptive_field(cfg: UNetConfig) -> int:
    """Analytic one-sided receptive field in voxels (paper SVI: halo must
    cover it). Each conv adds (k-1)/2 * stride_product; pooling doubles the
    effective stride on the way down and back up."""
    r = 0
    stride = 1
    half = (cfg.kernel_size - 1) // 2
    for i in range(cfg.depth):
        r += cfg.blocks_per_level * half * stride
        if i < cfg.depth - 1:
            stride *= 2
    for i in range(cfg.depth - 1):
        r += cfg.blocks_per_level * half * stride
        stride //= 2
    return r


def train_loss(params, cfg: UNetConfig, batch, continuity_weight: float = 0.0):
    """MSE + optional continuity (div u) penalty via central differences
    (paper SVI trains with an additional continuity constraint)."""
    pred = apply(params, cfg, batch["inputs"])
    mse = jnp.mean(jnp.square(pred - batch["targets"]))
    if continuity_weight:
        u = pred[..., :3]
        div = (jnp.gradient(u[..., 0], axis=1)
               + jnp.gradient(u[..., 1], axis=2)
               + jnp.gradient(u[..., 2], axis=3))
        mse = mse + continuity_weight * jnp.mean(jnp.square(div))
    return mse
