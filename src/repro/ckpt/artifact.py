"""Deploy artifacts: everything a restarted server needs to skip cold start.

A deploy artifact is one durable file (``repro.ckpt.checkpoint`` container)
bundling a trained server's *learned and compiled* state:

* model params + normalizer stats (what a training checkpoint carries),
* the autoscaler's learned state — target ladder, live bucket sizes and the
  request-size histogram — so a restored auto server resumes the adapted
  ladder instead of re-learning traffic from scratch,
* per-bucket **calibrated grid specs** (the host-cKDTree calibration result)
  so a restore — or a later LRU evict→rebuild — never re-pays calibration,
* per-bucket **AOT-serialized executables** (``jax.jit(...).lower()
  .compile()`` + ``jax.experimental.serialize_executable``) where the
  backend supports it, so the restored server's first request runs a
  deserialized program: zero traces, zero XLA compiles.

Executable serialization is backend-dependent (XLA:CPU in JAX 0.9.0 cannot
serialize executables at all; the TPU can), so :func:`serialize_compiled`
self-checks every payload by deserializing it immediately; a payload that
fails is dropped at save time and the restored server falls back to the
persistent compilation cache (``repro.ckpt.compile_cache``) — a re-trace
plus a disk load, still never a full compile.
"""
from __future__ import annotations

import logging
import pickle
from typing import Any, Optional

import jax

from repro.graphx.hashgrid import GridSpec
from repro.graphx.multiscale import MultiscaleSpec

from repro.ckpt import checkpoint as ckpt

log = logging.getLogger(__name__)

ARTIFACT_FORMAT = "xmgn-deploy-artifact-v1"


# ------------------------------------------------------- spec serialization

def pack_multiscale_spec(ms: MultiscaleSpec) -> dict:
    """MultiscaleSpec -> plain msgpack-able dict (calibration cache entry)."""
    return {
        "level_sizes": list(ms.level_sizes),
        "k": int(ms.k),
        "grids": [{
            "n_points": int(g.n_points), "k": int(g.k),
            "resolution": list(g.resolution),
            "neigh_cap": int(g.neigh_cap), "layout": g.layout,
        } for g in ms.grids],
    }


def unpack_multiscale_spec(d: dict) -> MultiscaleSpec:
    grids = tuple(GridSpec(n_points=int(g["n_points"]), k=int(g["k"]),
                           resolution=tuple(int(r) for r in g["resolution"]),
                           neigh_cap=int(g["neigh_cap"]),
                           layout=str(g["layout"]))
                  for g in d["grids"])
    return MultiscaleSpec(level_sizes=tuple(int(n) for n in d["level_sizes"]),
                          k=int(d["k"]), grids=grids)


def pack_shard_spec(spec) -> dict:
    """ShardSpec -> plain dict: the frozen sharded-program parameters
    (shard/halo topology, per-shard multiscale spec, calibrated halo width)
    a restored sharded server reuses instead of re-planning the reference."""
    return {
        "n_shards": int(spec.n_shards),
        "halo_hops": int(spec.halo_hops),
        "halo_width": float(spec.halo_width),
        "ms": pack_multiscale_spec(spec.ms),
    }


def unpack_shard_spec(d: dict):
    from repro.graphx.sharded import ShardSpec
    return ShardSpec(n_shards=int(d["n_shards"]),
                     halo_hops=int(d["halo_hops"]),
                     ms=unpack_multiscale_spec(d["ms"]),
                     halo_width=float(d.get("halo_width", 0.0)))


# ------------------------------------------------------------- AOT programs

def serialize_compiled(compiled) -> Optional[bytes]:
    """Serialize an AOT-compiled executable; ``None`` if unsupported.

    The payload is self-checked by deserializing it in-process: a backend
    that serializes but cannot re-link the program is caught HERE, at
    deploy time, rather than at restore time in production.
    """
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load, serialize)
        payload, in_tree, out_tree = serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        deserialize_and_load(*pickle.loads(blob))      # self-check
        return blob
    except Exception as e:
        log.warning("AOT executable serialization unsupported on backend "
                    "%r (%s: %s); artifact will rely on the persistent "
                    "compilation cache instead", jax.default_backend(),
                    type(e).__name__, e)
        return None


def deserialize_compiled(blob: bytes):
    """Load a serialized executable; ``None`` (with a warning) on failure —
    e.g. restoring a TPU artifact on a CPU host — so callers fall back to
    the compile path instead of dying."""
    try:
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        return deserialize_and_load(*pickle.loads(blob))
    except Exception as e:
        log.warning("could not deserialize AOT executable (%s: %s); "
                    "falling back to jit + compilation cache",
                    type(e).__name__, e)
        return None


# ----------------------------------------------------------- artifact file

def save_artifact(path: str, tree: dict) -> None:
    """Durably write an artifact (stamps format + backend)."""
    tree = dict(tree)
    tree["format"] = ARTIFACT_FORMAT
    tree["backend"] = jax.default_backend()
    ckpt.save(path, tree)


def load_artifact(path: str) -> dict:
    """Read + validate an artifact; raises ``CheckpointError`` on a corrupt
    file and ``ValueError`` on a non-artifact checkpoint."""
    tree = ckpt.restore(path)
    if not isinstance(tree, dict) or tree.get("format") != ARTIFACT_FORMAT:
        raise ValueError(
            f"{path!r} is not a deploy artifact (format="
            f"{tree.get('format') if isinstance(tree, dict) else None!r}, "
            f"expected {ARTIFACT_FORMAT!r}); train checkpoints load via "
            "GNNServer.from_checkpoint")
    if tree.get("backend") != jax.default_backend():
        log.warning("artifact %s was built for backend %r but this process "
                    "runs %r: AOT executables will be dropped and programs "
                    "recompiled (or served from the compilation cache)",
                    path, tree.get("backend"), jax.default_backend())
        tree = dict(tree, aot={})
    return tree
