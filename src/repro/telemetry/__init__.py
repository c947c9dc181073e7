"""``repro.telemetry`` — spans, metrics, and JAX profiler hooks.

One import gives the serving and training stacks a shared observability
surface:

* :class:`~repro.telemetry.trace.Tracer` — nestable, thread-safe spans with
  per-request ``trace_id``; JSONL + Chrome ``trace_event`` exporters.
* :class:`~repro.telemetry.metrics.MetricsRegistry` — counters, gauges,
  fixed-bucket streaming histograms; Prometheus text + JSON snapshots.
* :mod:`~repro.telemetry.profiler` — opt-in ``jax.profiler.trace``
  capture, device-memory snapshots.

The :class:`Telemetry` bundle is what call sites thread around: built from
``GNNConfig.telemetry`` / ``trace_dir`` knobs (or explicitly), it carries a
tracer that is a true no-op object when disabled — the serving hot path
pays nothing for instrumentation it is not using (bound pinned by
``tests/test_telemetry.py``). The metrics registry is *always* live: it is
the bounded-memory backing store for ``ServerStats`` and costs O(1) per
observation.

:meth:`Telemetry.span` is the one way a stage is recorded: one clock read
on entry and one on exit feed a stage histogram, a tracer span and a
``jax.profiler.TraceAnnotation`` — the last puts the stage on the
profiler's clock, beside the device's ops in a captured trace.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import jax

from repro.telemetry.metrics import (Counter, Gauge, Histogram,
                                     MetricsRegistry, SnapshotWriter,
                                     default_latency_buckets,
                                     default_size_buckets)
from repro.telemetry.trace import (_NULL_SPAN, NULL_TRACER, NullTracer,
                                   SpanRecord, Tracer, check_well_nested,
                                   make_tracer)
from repro.telemetry import profiler
from repro.telemetry.profiler import (device_memory_snapshot, trace_capture,
                                      warn_once)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "SnapshotWriter",
    "Tracer", "NullTracer", "NULL_TRACER", "SpanRecord", "Telemetry",
    "make_tracer", "check_well_nested", "trace_capture",
    "device_memory_snapshot", "warn_once", "profiler",
    "default_latency_buckets", "default_size_buckets",
]


class _Stage:
    """One stage through up to three sinks on one pair of clock reads.

    ``hist`` observes the duration, ``span`` (an unopened tracer span)
    records the interval and a ``jax.profiler.TraceAnnotation`` named
    ``annotation`` (with ``attrs`` as its metadata) encloses it on the
    profiler's clock; each is skipped when None. ``t0``/``t1`` keep the two
    reads for callers that need the interval's ends.
    """
    __slots__ = ("_hist", "_span", "_annotation", "_attrs", "_ann",
                 "t0", "t1")

    def __init__(self, hist, span=None, annotation=None, attrs=None):
        self._hist = hist
        self._span = span
        self._annotation = annotation
        self._attrs = attrs
        self._ann = None
        self.t0 = self.t1 = None

    def set(self, **attrs):
        """Attach attributes discovered mid-stage to the tracer span."""
        if self._span is not None:
            self._span.set(**attrs)
        return self

    def __enter__(self):
        if self._annotation is not None:
            self._ann = jax.profiler.TraceAnnotation(self._annotation,
                                                     **self._attrs)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        if self._span is not None:
            self._span.open(self.t0)
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._span is not None:
            self._span.close(self.t1)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(self.t1 - self.t0)
        return False


class Telemetry:
    """The bundle a server / trainer owns: tracer + metrics + capture flags.

    ``enabled`` gates the span tracer and the host ``TraceAnnotation``
    regions; the metrics registry stays live either way (it backs the
    always-on serving stats). ``trace_dir`` is where :meth:`export` drops
    artifacts; ``profile`` additionally captures a full ``jax.profiler``
    trace under ``<trace_dir>/jax_profile`` for the duration of
    :meth:`capture`.
    """

    def __init__(self, enabled: bool = False,
                 trace_dir: Optional[str] = None, profile: bool = False,
                 max_spans: int = 65536,
                 metrics: Optional[MetricsRegistry] = None):
        self.enabled = bool(enabled)
        self.trace_dir = trace_dir or None
        self.profile = bool(profile) and self.enabled
        self.tracer = make_tracer(self.enabled, max_spans=max_spans)
        self.metrics = metrics if metrics is not None else MetricsRegistry()

    @classmethod
    def disabled(cls) -> "Telemetry":
        return cls(enabled=False)

    @classmethod
    def from_config(cls, cfg, **kw) -> "Telemetry":
        """Build from ``GNNConfig``-style knobs (``telemetry``,
        ``trace_dir``, ``profile_capture``), tolerant of configs that
        predate them."""
        return cls(enabled=getattr(cfg, "telemetry", False),
                   trace_dir=getattr(cfg, "trace_dir", "") or None,
                   profile=getattr(cfg, "profile_capture", False), **kw)

    # ------------------------------------------------------------- tracing

    def span(self, name: str, trace_id: Optional[str] = None, *,
             scope: Optional[str] = None, hist: Optional[Histogram] = None,
             **attrs):
        """A stage as a context manager: ``with tel.span("prepare",
        scope="serve", hist=h, bucket=256):``.

        One ``perf_counter`` read on entry and one on exit feed every sink:
        ``hist`` (always, when given) observes the duration; with telemetry
        on, the tracer records a span ``name`` with ``attrs``, and a
        ``scope`` opens a ``jax.profiler.TraceAnnotation`` named
        ``<scope>/<name>`` with ``attrs`` as its metadata (so give only
        strings and numbers). With telemetry off and no ``hist`` this is the
        shared no-op span; with a ``hist`` it costs two clock reads and one
        observation, and builds no annotation.
        """
        if not self.enabled:
            return _NULL_SPAN if hist is None else _Stage(hist)
        return _Stage(hist, self.tracer.span(name, trace_id=trace_id,
                                             **attrs),
                      f"{scope}/{name}" if scope else None, attrs)

    def trace(self, trace_id: Optional[str]):
        return self.tracer.trace(trace_id)

    def capture(self):
        """Opt-in full ``jax.profiler`` capture for a ``with`` region."""
        log_dir = (os.path.join(self.trace_dir, "jax_profile")
                   if (self.profile and self.trace_dir) else None)
        return trace_capture(log_dir)

    # ------------------------------------------------------------- export

    def export(self, trace_dir: Optional[str] = None) -> dict:
        """Write every artifact into ``trace_dir``; returns their paths.

        Artifacts: ``trace.jsonl`` (span-per-line), ``trace_chrome.json``
        (chrome://tracing), ``metrics.prom`` (Prometheus text),
        ``metrics.json`` (snapshot incl. device-memory stats).
        """
        trace_dir = trace_dir or self.trace_dir
        if not trace_dir:
            raise ValueError("no trace_dir configured for telemetry export")
        os.makedirs(trace_dir, exist_ok=True)
        paths = {
            "trace_jsonl": os.path.join(trace_dir, "trace.jsonl"),
            "trace_chrome": os.path.join(trace_dir, "trace_chrome.json"),
            "metrics_prom": os.path.join(trace_dir, "metrics.prom"),
            "metrics_json": os.path.join(trace_dir, "metrics.json"),
        }
        self.tracer.export_jsonl(paths["trace_jsonl"])
        self.tracer.export_chrome_trace(paths["trace_chrome"])
        with open(paths["metrics_prom"], "w") as f:
            f.write(self.metrics.prometheus_text())
        self.metrics.write_snapshot(
            paths["metrics_json"],
            extra={"device_memory": device_memory_snapshot()})
        return paths

    def snapshot_writer(self, interval_s: float = 5.0) -> SnapshotWriter:
        """Periodic JSON snapshot writer into ``<trace_dir>/metrics.json``."""
        if not self.trace_dir:
            raise ValueError("no trace_dir configured for snapshot writer")
        os.makedirs(self.trace_dir, exist_ok=True)
        return SnapshotWriter(self.metrics,
                              os.path.join(self.trace_dir, "metrics.json"),
                              interval_s=interval_s)
