"""Operations and bytes the X-UNet3D forward pass needs, from a config's
widths and the shape of the voxels it runs on.

Only convolutions count as operations: 2 per multiply-add, over the taps
that land inside the volume (a ``SAME`` convolution's taps on its zero
padding are no work), which is how XLA's ``cost_analysis`` counts them; a
1x1 convolution of a nearest-upsampled tensor counts at the coarse level
it commutes with.
Bytes are the least any implementation must move: the input features read
and the output fields written, float32, and the weights once.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from bench.reference_unet import conv_shapes

F32 = 4


def conv_layers(cfg: dict, shape: Sequence[int]
                ) -> List[Tuple[Tuple[int, int, int], int, int, int]]:
    """(input shape (X', Y', Z'), kernel, c_in, c_out) of every convolution
    of one forward pass over a volume of ``shape`` voxels (each a multiple
    of ``2 ** (depth - 1)``)."""
    s = conv_shapes(cfg)
    at = [tuple(n // 2 ** i for n in shape) for i in range(cfg["depth"])]
    out = []
    for i, blk in enumerate(s["enc"]):
        out += [(at[i], *c) for c in blk]
    for j, i in enumerate(reversed(range(cfg["depth"] - 1))):
        # 1x1 convs of an upsampled tensor are no more work than at the
        # coarse level they commute with (the up conv and the gate's wg)
        out.append((at[i + 1], *s["ups"][j]))
        g = s["gates"][j]
        out += [(at[i], *g["wx"]), (at[i + 1], *g["wg"]), (at[i], *g["psi"])]
        out += [(at[i], *c) for c in s["dec"][j]]
    out.append((at[0], *s["head"]))
    return out


def conv_flops(shape: Sequence[int], k: int, cin: int, cout: int) -> int:
    """One ``SAME`` convolution: 2 x c_in x c_out per tap inside the
    volume; along an axis of n voxels a k-wide kernel has n k - h (h + 1)
    such taps, h = (k - 1) / 2."""
    h = (k - 1) // 2
    taps = 1
    for n in shape:
        taps *= n * k - h * (h + 1)
    return 2 * cin * cout * taps


def forward_flops(cfg: dict, shape: Sequence[int]) -> int:
    """One forward pass over ``shape`` voxels."""
    return sum(conv_flops(*layer) for layer in conv_layers(cfg, shape))


def weight_bytes(cfg: dict) -> int:
    n = sum(k ** 3 * cin * cout + cout
            for _, k, cin, cout in conv_layers(cfg, cfg["grid"]))
    return F32 * n


def forward_bytes(cfg: dict, shape: Sequence[int]) -> int:
    """Least bytes of one forward pass over ``shape`` voxels."""
    voxels = shape[0] * shape[1] * shape[2]
    return (F32 * voxels * (cfg["in_channels"] + cfg["out_channels"])
            + weight_bytes(cfg))


def slab_shape(cfg: dict, window: int) -> Tuple[int, int, int]:
    _, y, z = cfg["grid"]
    return (window, y, z)


def slab_windows(cfg: dict) -> Tuple[int, int, int]:
    """(slabs, owned planes, window planes) of the program's partitioning:
    ``n_partitions`` equal owned widths of ``ceil(X / n)`` rounded up to
    the pooling alignment, each window ``halo`` (rounded up alike) wider on
    both sides and clamped to the domain."""
    x = cfg["grid"][0]
    align = 2 ** (cfg["depth"] - 1)
    n = cfg["n_partitions"]
    per = -(-x // n)
    owned = min(-(-per // align) * align, x)
    halo = -(-cfg["halo"] // align) * align
    return n, owned, min(owned + 2 * halo, x)


def served_flops(cfg: dict) -> int:
    """The U-Net operations of one served geometry: every slab window."""
    n, _, window = slab_windows(cfg)
    return n * forward_flops(cfg, slab_shape(cfg, window))


def served_bytes(cfg: dict) -> int:
    n, _, window = slab_windows(cfg)
    return n * forward_bytes(cfg, slab_shape(cfg, window))


def domain_flops(cfg: dict) -> int:
    """One forward pass over the whole grid, each voxel once."""
    return forward_flops(cfg, cfg["grid"])
