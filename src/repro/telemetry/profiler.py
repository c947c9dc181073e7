"""JAX profiler integration: capture and memory snapshots.

Host-side stages reach a captured profile as ``TraceAnnotation`` regions
through :meth:`repro.telemetry.Telemetry.span`. Inside jitted code
``jax.named_scope`` labels instead — an annotation there would time
*tracing*, not execution; named scopes ride into the HLO metadata and label
the compiled program's ops in the profile. The graphx pipeline and the
MeshGraphNet processor carry those scopes (``graphx/knn_edges``,
``graphx/featurize``, ``mgn/message_passing``...).

Two hooks, both opt-in and safe to call when telemetry is disabled:

* :func:`trace_capture` — wraps ``jax.profiler.trace(log_dir)`` so a
  serving run / training run can drop a full XLA profile under
  ``<trace_dir>/jax_profile`` when the capture flag is set; a no-op
  nullcontext otherwise (and degrades to a warning if the runtime lacks
  profiler support, e.g. stripped CPU wheels).
* :func:`device_memory_snapshot` — per-device ``memory_stats()`` dump
  (bytes in use / peak / limit where the backend reports them; CPU
  backends typically report nothing and get ``None``).
"""
from __future__ import annotations

import contextlib
import logging
import threading
from typing import Optional

import jax

log = logging.getLogger(__name__)


@contextlib.contextmanager
def trace_capture(log_dir: Optional[str]):
    """Capture a full ``jax.profiler`` trace into ``log_dir`` (TensorBoard
    ``trace_viewer`` / Perfetto format). ``log_dir=None`` is a no-op, so
    callers gate the capture with one argument."""
    if not log_dir:
        yield None
        return
    try:
        jax.profiler.start_trace(log_dir)
    except Exception as e:                 # profiler unavailable: don't kill
        log.warning("jax.profiler trace capture unavailable: %r", e)
        yield None
        return
    try:
        yield log_dir
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("jax.profiler stop_trace failed: %r", e)


def device_memory_snapshot() -> list:
    """One ``memory_stats()`` record per device (None where unsupported).

    Keyed for the JSON snapshot: ``[{"device": "cpu:0", "stats": {...}}]``.
    """
    out = []
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats is not None:              # ints only: keep it JSON-clean
            stats = {k: v for k, v in stats.items()
                     if isinstance(v, (int, float))}
        out.append({"device": str(d), "platform": d.platform,
                    "stats": stats})
    return out


class _WarnOnce:
    """Per-condition log dedup: first occurrence warns at WARNING, repeats
    are counted and logged at DEBUG — sustained bad traffic cannot flood
    the log with one line per request."""

    def __init__(self, logger: logging.Logger):
        self._log = logger
        self._seen: dict = {}
        self._lock = threading.Lock()

    def __call__(self, key, msg: str) -> bool:
        """Returns True when this was the first occurrence of ``key``."""
        with self._lock:
            n = self._seen.get(key, 0)
            self._seen[key] = n + 1
        if n == 0:
            self._log.warning("%s", msg)
            return True
        self._log.debug("%s (repeat %d)", msg, n)
        return False

    def count(self, key) -> int:
        with self._lock:
            return self._seen.get(key, 0)

    def reset(self):
        with self._lock:
            self._seen.clear()


def warn_once(logger: logging.Logger) -> _WarnOnce:
    """Build a warn-once gate bound to a module logger."""
    return _WarnOnce(logger)
