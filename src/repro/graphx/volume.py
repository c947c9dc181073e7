"""Voxel input features of the X-UNet3D volume model, built on the device
from a request's sampled surface cloud.

The definition, shared by the serving program and the plain references:
voxel ``v`` of the grid (``grid`` voxels of ``voxel`` metres from
``box_lo``, centres at ``box_lo + (i + 1/2) voxel``) takes ``q(v)``, the
nearest sample ``p`` of the cloud ``P`` with ``|v - p| <= c`` (``c`` is
``sdf_band`` voxels) if there is one, else the nearest of ``P[:M]`` (``M``
is ``sdf_prefix``). With ``n_q`` the sample's normal,

    sdf(v) = sign((v - q) . n_q) |v - q|,   grad(v) = sign (v - q) / |v - q|

(0 where ``|v - q| = 0``). The 16 channels are ``[x, y, z, sin pi x,
cos pi x, sin 2 pi x, sdf, grad]`` with the trig terms per axis.

On the device every distance is exact (float32) and nothing is gathered
per voxel: a TPU gathers a few hundred million elements a second, and a
request has 54.5M voxels. Each search instead carries the nearest
sample's coordinates and normal through its min-reduction.

- Band search: the samples are binned into cells of ``sdf_band`` voxels a
  side aligned with the grid, so every sample within ``c`` of a voxel lies
  in the 27 cells around the voxel's cell. A dense table holds each cell's
  samples (at most ``cell_cap``; one scatter of the cloud per chunk of
  cells), and every voxel reduces over its 27 neighbour cells' slots,
  computed cell-major so a cell's voxels share the table's values.
- Fallback: every voxel's nearest of ``P[:M]`` by brute force, the squared
  distance summed ``(dx^2 + dy^2) + dz^2`` as the band's is.
- :func:`sdf_grid` makes the signed distance and its gradient for the
  whole grid once per request (4 channels); :func:`slab_features` adds
  the coordinate channels per slab window, so the 16 channels never exist
  for the whole grid. A cloud with more than ``cell_cap`` samples in one
  cell is refused on the host (:func:`overflow_count`), as the kNN grid's
  overflow is.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

CELL_CHUNK = 30          # cell planes (of band voxels) per search step
FAR = 1e9                # coordinate of an empty table slot
CELL_SAFETY = 1.5        # cell capacity over the reference cloud's fullest
_OFFSETS = np.array([(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1)
                     for dz in (-1, 0, 1)], np.int32)          # (27, 3)


@dataclass(frozen=True)
class VolumeSpec:
    """Static shapes of one volume bucket's feature program."""
    n_points: int                     # cloud length
    grid: Tuple[int, int, int]        # voxels per axis
    voxel: float                      # voxel edge, metres
    box_lo: Tuple[float, float, float]
    band: int                         # band radius and cell edge, voxels
    prefix: int                       # fallback: the cloud's first samples
    cell_cap: int                     # samples one cell's table row holds

    @property
    def cells(self) -> Tuple[int, int, int]:
        return tuple(-(-g // self.band) for g in self.grid)


def spec_for(cfg, n_points: int, cell_cap: int) -> VolumeSpec:
    """A spec from a ``UNetConfig`` and the per-cell capacity."""
    return VolumeSpec(n_points=int(n_points), grid=tuple(cfg.grid),
                      voxel=float(cfg.voxel), box_lo=tuple(cfg.box_lo),
                      band=int(cfg.sdf_band),
                      prefix=min(int(cfg.sdf_prefix), int(n_points)),
                      cell_cap=int(cell_cap))


def voxel_axes(spec: VolumeSpec, extent=None):
    """Voxel-centre coordinates per axis, float32 (rounded from float64);
    ``extent`` voxels per axis, the grid's by default."""
    extent = extent or spec.grid
    return tuple((lo + (np.arange(g) + 0.5) * spec.voxel).astype(np.float32)
                 for lo, g in zip(spec.box_lo, extent))


# ------------------------------------------------------------ host checks

def cell_coords(pts: np.ndarray, spec: VolumeSpec) -> np.ndarray:
    """Band-search cell of each sample, clipped into the grid (a sample
    outside the box joins the nearest cell: an extra candidate only)."""
    edge = np.float32(spec.band * spec.voxel)
    lo = np.asarray(spec.box_lo, np.float32)
    cc = np.floor((np.asarray(pts, np.float32) - lo) / edge).astype(np.int64)
    return np.clip(cc, 0, np.asarray(spec.cells) - 1)


def cell_counts(pts: np.ndarray, spec: VolumeSpec) -> np.ndarray:
    """Samples per occupied cell."""
    cc = cell_coords(pts, spec)
    _, cy, cz = spec.cells
    return np.unique((cc[:, 0] * cy + cc[:, 1]) * cz + cc[:, 2],
                     return_counts=True)[1]


def overflow_count(pts: np.ndarray, spec: VolumeSpec) -> int:
    """Samples the band search's cell table would drop for this cloud
    (beyond ``cell_cap`` in a cell). Zero means the search is exact."""
    return int(np.maximum(cell_counts(pts, spec) - spec.cell_cap, 0).sum())


def calibrate_spec(ref_pts: np.ndarray, cfg, n_points: int) -> VolumeSpec:
    """The per-cell capacity from a reference cloud with ``CELL_SAFETY``
    headroom, rounded up to 8."""
    probe = spec_for(cfg, n_points, 0)
    most = int(cell_counts(ref_pts, probe).max(initial=1))
    cap = int(math.ceil(most * CELL_SAFETY))
    return spec_for(cfg, n_points, -(-cap // 8) * 8)


# ------------------------------------------------------------ device side

def _nearest(d2, attrs, axis: int):
    """Along ``axis``: the least ``d2`` and the ``attrs`` (broadcast to
    ``d2``'s shape) at its first occurrence, in one reduction."""
    attrs = tuple(jnp.broadcast_to(a, d2.shape) for a in attrs)

    def pick(a, b):
        take_b = b[0] < a[0]
        return tuple(jnp.where(take_b, y, x) for x, y in zip(a, b))

    init = (jnp.float32(jnp.inf),) + (jnp.float32(0),) * len(attrs)
    out = jax.lax.reduce((d2,) + attrs, init, pick, (axis,))
    return list(out)


def _closer(best, cand):
    """Elementwise: ``cand`` where its distance is strictly less."""
    take = cand[0] < best[0]
    return [jnp.where(take, c, b) for b, c in zip(best, cand)]


def sdf_grid(points, normals, n_valid, spec: VolumeSpec):
    """Signed distance and its gradient for every voxel: (4, X, Y, Z)
    float32, channels ``[sdf, gx, gy, gz]`` (module docstring)."""
    points = points.astype(jnp.float32)
    normals = normals.astype(jnp.float32)
    n = points.shape[0]
    b, S = spec.band, spec.cell_cap
    cx, cy, cz = spec.cells
    nc = min(CELL_CHUNK, cx)
    chunks = -(-cx // nc)
    valid = jnp.arange(n) < n_valid
    attrs = [points[:, i] for i in range(3)] + [normals[:, i]
                                                for i in range(3)]

    # each sample's cell and its rank among its cell's samples
    edge = jnp.float32(spec.band * spec.voxel)
    cc = jnp.floor((points - jnp.asarray(spec.box_lo, jnp.float32)) / edge)
    cc = jnp.clip(cc.astype(jnp.int32), 0, jnp.asarray(spec.cells) - 1)
    cid = jnp.where(valid, (cc[:, 0] * cy + cc[:, 1]) * cz + cc[:, 2],
                    cx * cy * cz)
    order = jnp.argsort(cid)
    s_cid = cid[order]
    rank = jnp.arange(n) - jnp.searchsorted(s_cid, s_cid, side="left")
    s_cc = cc[order]
    slot = jnp.where((rank < S) & (s_cid < cx * cy * cz), rank, S)
    s_attrs = jnp.stack([a[order] for a in attrs])              # (6, N)

    # voxel-centre coordinates as (local, cell) tables: voxel i = b c + l
    ext = (chunks * nc * b, cy * b, cz * b)
    ax = [jnp.asarray(a.reshape(-1, b).T) for a in voxel_axes(spec, ext)]
    c2 = jnp.float32((spec.band * spec.voxel) ** 2)
    m = spec.prefix
    pm = [a[:m] for a in attrs]
    far_m = jnp.arange(m) >= n_valid
    dy2 = jnp.square(ax[1][:, :, None] - pm[1])                 # (b, cy, M)
    dz2 = jnp.square(ax[2][:, :, None] - pm[2])                 # (b, cz, M)
    shape6 = (b, b, b, nc, cy, cz)      # (lx, ly, lz, cx, cy, cz)

    def spread(t, k):
        """A (b, cells) table of axis ``k`` laid over ``shape6``."""
        dims = [1] * 6
        dims[k], dims[3 + k] = t.shape
        return t.reshape(dims)

    def chunk(out, c0):
        xs = jax.lax.dynamic_slice_in_dim(ax[0], c0, nc, axis=1)  # (b, nc)
        v = [spread(xs, 0), spread(ax[1], 1), spread(ax[2], 2)]
        # this chunk's cell table, one empty cell of margin on every side
        tx = s_cc[:, 0] - c0 + 1
        tx = jnp.where((tx >= 0) & (tx < nc + 2), tx, nc + 2)  # else drop
        table = jnp.full((6, S, nc + 2, cy + 2, cz + 2), FAR, jnp.float32)
        table = table.at[:, slot, tx, s_cc[:, 1] + 1, s_cc[:, 2] + 1].set(
            s_attrs, mode="drop")
        def neighbour(best, d):
            """Fold the cell at offset ``d - 1`` into every voxel's best."""
            t = jax.lax.dynamic_slice(table, (0, 0, d[0], d[1], d[2]),
                                      (6, S, nc, cy, cz))
            t = t[:, None, None, None]                # (6, 1, 1, 1, S, ...)
            d2 = (jnp.square(v[0][:, :, :, None] - t[0])
                  + jnp.square(v[1][:, :, :, None] - t[1])) \
                + jnp.square(v[2][:, :, :, None] - t[2])
            d2 = jnp.where(d2 <= c2, d2, jnp.inf)
            return _closer(best, _nearest(d2, list(t), 3)), None

        best = [jnp.full(shape6, jnp.inf)] + [jnp.zeros(shape6)] * 6
        best, _ = jax.lax.scan(neighbour, best, jnp.asarray(_OFFSETS + 1))
        # fallback over the prefix, where the band held no sample
        dx2 = jnp.square(xs[:, :, None] - pm[0])                # (b, nc, M)
        dxy = dx2.reshape(b, 1, 1, nc, 1, 1, m) \
            + dy2.reshape(1, b, 1, 1, cy, 1, m)
        d2 = jnp.where(far_m, jnp.inf, dxy) + dz2.reshape(1, 1, b, 1, 1, cz, m)
        fall = _nearest(d2, pm, 6)
        near = jnp.isfinite(best[0])
        q = [jnp.where(near, p, f) for p, f in zip(best[1:], fall[1:])]
        dv = [v[k] - q[k] for k in range(3)]
        dist = jnp.sqrt(dv[0] * dv[0] + dv[1] * dv[1] + dv[2] * dv[2])
        s = jnp.sign(dv[0] * q[3] + dv[1] * q[4] + dv[2] * q[5])
        inv = jnp.where(dist > 0, s / jnp.where(dist > 0, dist, 1.0), 0.0)
        g = jnp.stack([s * dist] + [g * inv for g in dv])  # (4, *shape6)
        # cell-major -> voxel-major, z then y then x
        for k in (2, 1, 0):
            g = _interleave([g[(slice(None),) * (1 + k) + (l,)]
                             for l in range(b)], g.ndim - 4 + k)
        return jax.lax.dynamic_update_slice(out, g, (0, c0 * b, 0, 0)), None

    out = jnp.zeros((4, chunks * nc * b, cy * b, cz * b), jnp.float32)
    out, _ = jax.lax.scan(chunk, out,
                          jnp.arange(chunks, dtype=jnp.int32) * nc)
    X, Y, Z = spec.grid
    return out[:, :X, :Y, :Z]


def _interleave(parts, axis: int):
    """``out[b i + l] = parts[l][i]`` along ``axis``, by interior padding:
    a TPU pads a (cells, b) minor pair to 128 lanes if it is reshaped."""
    b = len(parts)
    out = 0.0
    for l, part in enumerate(parts):
        pad = [(0, 0, 0)] * part.ndim
        pad[axis] = (l, b - 1 - l, b - 1)
        out = out + jax.lax.pad(part, jnp.float32(0), pad)
    return out


def slab_features(sdf, x0, width: int, spec: VolumeSpec):
    """The 16 input channels (1, width, Y, Z, 16) of the planes
    ``[x0, x0 + width)``: coordinates and their trig terms from the grid,
    signed distance and gradient sliced from :func:`sdf_grid`'s output."""
    xs, ys, zs = (jnp.asarray(a) for a in voxel_axes(spec))
    shape = (width,) + tuple(spec.grid[1:])
    x = jax.lax.dynamic_slice_in_dim(xs, x0, width)
    v = (jnp.broadcast_to(x[:, None, None], shape),
         jnp.broadcast_to(ys[None, :, None], shape),
         jnp.broadcast_to(zs[None, None, :], shape))
    g = jax.lax.dynamic_slice_in_dim(sdf, x0, width, axis=1)
    chans = (list(v) + [jnp.sin(np.pi * c) for c in v]
             + [jnp.cos(np.pi * c) for c in v]
             + [jnp.sin(2 * np.pi * c) for c in v] + list(g))
    return jnp.stack(chans, axis=-1)[None]
