"""Halo partitioning for voxel domains (paper SVI): the same scheme as the
graph case, applied to a 3D UNet. A partition is a slab of the domain along
one axis, extended by a halo that must cover the network's receptive field;
outputs on the halo are discarded and owned outputs stitched together —
exactly equal to the full-domain forward pass when halo >= receptive field.

Includes the paper's *empirical receptive-field finder*: run the network on a
full domain and on partitioned domains with growing halo; the smallest halo
whose stitched output matches is the receptive field.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SlabWindows(NamedTuple):
    """Equal-width slabs of ``[0, extent)``: slab ``i`` owns
    ``[owned_starts[i], owned_starts[i] + owned)`` and is computed over the
    window ``[window_starts[i], window_starts[i] + window)``."""
    owned: int
    window: int
    owned_starts: Tuple[int, ...]
    window_starts: Tuple[int, ...]

    @property
    def n(self) -> int:
        return len(self.owned_starts)


def slab_windows(extent: int, n_parts: int, halo: int,
                 align: int = 1) -> SlabWindows:
    """Split ``[0, extent)`` into ``n_parts`` slabs of one owned width and
    one window width, so that one compiled body serves every slab.

    The owned width is ``ceil(extent / n_parts)`` rounded up to ``align``;
    the last slab is shifted back to end at ``extent`` (an uneven last
    slab re-owns planes of its neighbour, with the same values). Each
    window adds the halo (rounded up to ``align``) on both sides and is
    clamped into the domain, never padded outside it: a convolution of
    zeros plus a bias is not zero, so padding would change the owned
    values. Starts are multiples of ``align`` so pooling windows coincide
    with the full domain's."""
    assert extent % align == 0, (extent, align)
    halo_u = -(-halo // align) * align
    per = -(-extent // n_parts)
    owned = min(-(-per // align) * align, extent)
    window = min(owned + 2 * halo_u, extent)
    own = [min(i * owned, extent - owned) for i in range(n_parts)]
    win = [min(max(o - halo_u, 0), extent - window) for o in own]
    return SlabWindows(owned, window, tuple(own), tuple(win))


def apply_partitioned(apply_fn: Callable, x, n_parts: int, halo: int,
                      axis: int = 1, align: int = 1):
    """Run ``apply_fn`` independently on each halo-extended slab of ``x``
    (axis is the spatial axis, default 1 = X of NDHWC) and stitch owned
    outputs. Mirrors paper SIII-D inference: predictions on halo nodes are
    discarded, the rest aggregated to reconstruct the full-domain output.
    The slab loop is :func:`scan_slabs`, the one the serving program runs."""
    sw = slab_windows(x.shape[axis], n_parts, halo, align)

    def slab(w0):
        y = apply_fn(jax.lax.dynamic_slice_in_dim(x, w0, sw.window, axis))
        return jnp.moveaxis(y, axis, 1)

    y = jax.eval_shape(slab, 0)
    shape = y.shape[:1] + (x.shape[axis],) + y.shape[2:]
    return jnp.moveaxis(scan_slabs(slab, sw, shape, y.dtype), 1, axis)


def scan_slabs(slab_fn: Callable, sw: SlabWindows, out_shape, dtype):
    """The slab loop inside one program: ``lax.scan`` over the windows of
    ``sw`` along axis 1 of an output ``out_shape`` (C, X, ...).
    ``slab_fn(window_start)`` returns the window's output (C, window, ...);
    its owned planes are written into the output with
    ``dynamic_update_slice`` (a shifted last slab rewrites planes of its
    neighbour with the same values). One slab body is compiled."""
    starts = jnp.asarray(np.stack([sw.window_starts, sw.owned_starts], -1),
                         jnp.int32)

    def body(out, st):
        w0, o0 = st[0], st[1]
        y = jax.lax.dynamic_slice_in_dim(slab_fn(w0), o0 - w0, sw.owned,
                                         axis=1)
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(
            out, y.astype(dtype), (zero, o0) + (zero,) * (len(out_shape) - 2)
        ), None

    out, _ = jax.lax.scan(body, jnp.zeros(out_shape, dtype), starts)
    return out


def find_receptive_halo(apply_fn: Callable, x, *, axis: int = 1,
                        n_parts: int = 2, align: int = 1,
                        max_halo: int = 64, tol: float = 1e-5) -> int:
    """Paper SVI empirical approach: 'run the network on a full domain and
    compare with a partitioned domain using varying halo sizes; the smallest
    halo for which the two outputs match indicates the minimum required
    receptive field size.'"""
    full = apply_fn(x)
    halo = align
    while halo <= max_halo:
        part = apply_partitioned(apply_fn, x, n_parts, halo, axis, align)
        if float(jnp.max(jnp.abs(part - full))) <= tol:
            return halo
        halo += align
    raise ValueError(f"no halo <= {max_halo} reproduces the full output")
