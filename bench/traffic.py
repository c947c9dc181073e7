"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

A mix names the cell module that drives it (``kind``: ``bench/cells/
<kind>.py``), a pool of car geometries (parametric car-like surfaces, seeded
by sample id), the points per request and the server's batching; a closed
loop keeps ``outstanding`` geometries in flight (a design sweep), an open
loop sends ``rate_per_s`` Poisson arrivals (interactive users). The run's
seed only reorders the work: every seed sends the same geometries and, in an
open loop, the same set of gaps between arrivals, in another order.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Iterator, List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if not os.path.isfile(os.path.join(HERE, "cells", f"{mix['kind']}.py")):
        raise FileNotFoundError(f"traffic {name}: no cell module "
                                f"bench/cells/{mix['kind']}.py")
    return mix


def car_params(sample_id: int) -> dict:
    """Proportions of car ``sample_id`` (a DrivAer-like morph family)."""
    rng = np.random.default_rng(1000 + sample_id)
    keys = (("length", 3.5, 5.2), ("width", 1.6, 2.1), ("height", 1.1, 1.6),
            ("cabin_height", 0.25, 0.55), ("cabin_pos", -0.15, 0.25),
            ("taper", 0.0, 0.5), ("power", 2.2, 3.5))
    return {k: float(rng.uniform(lo, hi)) for k, lo, hi in keys}


def car_surface(p: dict, nu: int, nv: int) -> Tuple[np.ndarray, np.ndarray]:
    """Closed triangulated car-like surface: a superellipsoid body with a
    cabin bump and a rear taper, ``2 * nu * (nv - 1)`` triangles. Returns
    (vertices (nu*nv, 3) float32, faces (F, 3) int64)."""
    u = np.linspace(0.0, 2 * np.pi, nu, endpoint=False)
    v = np.linspace(1e-3, np.pi - 1e-3, nv)
    uu, vv = np.meshgrid(u, v, indexing="ij")
    e = 2 / p["power"]

    def spow(x):
        return np.sign(x) * np.abs(x) ** e

    x = spow(np.sin(vv)) * spow(np.cos(uu)) * p["length"] / 2
    y = spow(np.sin(vv)) * spow(np.sin(uu)) * p["width"] / 2
    z = spow(np.cos(vv)) * p["height"] / 2
    z = z + p["cabin_height"] * np.exp(
        -((x / p["length"] - p["cabin_pos"]) / 0.18) ** 2) \
        * np.clip(z, 0, None) / (p["height"] / 2)
    y = y * (1.0 - p["taper"] * np.clip(x / (p["length"] / 2), 0, 1) ** 2)
    verts = np.stack([x, y, z], axis=-1).reshape(-1, 3).astype(np.float32)
    i, j = np.meshgrid(np.arange(nu), np.arange(nv - 1), indexing="ij")
    a = i * nv + j
    b = ((i + 1) % nu) * nv + j
    c, d = b + 1, a + 1
    faces = np.stack([np.stack([a, b, c], -1), np.stack([a, c, d], -1)],
                     axis=2).reshape(-1, 3)
    return verts, faces.astype(np.int64)


def geometry_pool(mix: dict) -> List[Tuple[np.ndarray, np.ndarray]]:
    return [car_surface(car_params(g), mix["nu"], mix["nv"])
            for g in mix["pool_ids"]]


def closed_order(mix: dict, seed: int) -> Iterator[int]:
    """Pool indices without end: each pass over the pool in a seeded order."""
    rng = np.random.default_rng([int(seed), 1])
    n = len(mix["pool_ids"])
    for _ in itertools.count():
        yield from (int(i) for i in rng.permutation(n))


def open_schedule(mix: dict, seed: int, span_s: float
                  ) -> List[Tuple[float, int]]:
    """(offset in seconds from the span's start, pool index) per request.

    ``round(rate * span)`` arrivals whose gaps are the evenly spaced
    quantiles of the exponential distribution, scaled to sum to the span and
    shuffled by the seed: Poisson-like arrivals, the same count and the same
    gaps for every seed. The first arrival is at offset 0.
    """
    rng = np.random.default_rng([int(seed), 2])
    n = max(int(round(mix["rate_per_s"] * span_s)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps * (span_s / gaps.sum()))
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    order = rng.permutation(np.arange(n) % len(mix["pool_ids"]))
    return [(float(t), int(i)) for t, i in zip(offsets, order)]
