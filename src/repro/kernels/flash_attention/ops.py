"""Jitted wrapper: (B, S, H, hd) model-layout API over the flash kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def mha(q, k, v, *, causal: bool = True, window: Optional[int] = None,
        softcap: Optional[float] = None):
    """q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd); GQA via H % KV == 0.
    Returns (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    assert h % kvh == 0
    gs = h // kvh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, -1, hd)
    o = flash_attention(qf, kf, vf, group_size=gs, causal=causal,
                        window=window, softcap=softcap)
    return o.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
