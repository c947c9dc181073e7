"""repro.telemetry: span tracer, metrics registry, profiler hooks, and the
serving/training integration (ServerStats backward compat, zero-cost-when-off
guarantees, exporter formats)."""
import json
import logging
import math
import os
import re
import threading
import time

import numpy as np
import pytest

from repro.telemetry import (NULL_TRACER, Counter, Gauge, Histogram,
                             MetricsRegistry, NullTracer, SnapshotWriter,
                             Telemetry, Tracer, check_well_nested,
                             default_latency_buckets, default_size_buckets,
                             make_tracer, warn_once)
from repro.telemetry.trace import _NULL_SPAN


# ------------------------------------------------------------------ tracer

def test_span_nesting_and_attrs():
    tr = Tracer()
    with tr.span("outer", trace_id="req-1", bucket=256) as outer:
        with tr.span("inner") as inner:
            inner.set(n=3)
    recs = tr.records()
    assert [r.name for r in recs] == ["inner", "outer"]
    inner_r, outer_r = recs
    assert inner_r.parent_id == outer_r.span_id
    assert outer_r.parent_id is None
    # trace_id inherited from the enclosing span
    assert inner_r.trace_id == "req-1" and outer_r.trace_id == "req-1"
    assert outer_r.attrs == {"bucket": 256}
    assert inner_r.attrs == {"n": 3}
    assert inner_r.t_start >= outer_r.t_start - 1e-6
    assert inner_r.t_end <= outer_r.t_end + 1e-6
    assert check_well_nested(recs) == []


def test_trace_context_binds_default_trace_id():
    tr = Tracer()
    with tr.trace("step-7"):
        with tr.span("a"):
            pass
    with tr.span("b"):
        pass
    a, b = tr.records()
    assert a.trace_id == "step-7"
    assert b.trace_id is None


def test_span_thread_hammer_well_nested():
    """Many threads, deep nesting, no cross-thread leakage."""
    tr = Tracer(max_spans=100_000)
    n_threads, n_iter = 8, 40

    def work(tid):
        for i in range(n_iter):
            with tr.trace(f"t{tid}-{i}"):
                with tr.span("outer", tid=tid):
                    with tr.span("mid"):
                        with tr.span("leaf"):
                            pass

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.records()
    assert len(recs) == n_threads * n_iter * 3
    assert tr.dropped() == 0
    assert check_well_nested(recs) == []
    # every span picked up the thread's bound trace_id
    assert all(r.trace_id and r.trace_id.startswith("t") for r in recs)


def test_bounded_span_buffer_drops_oldest():
    tr = Tracer(max_spans=10)
    for i in range(25):
        with tr.span(f"s{i}"):
            pass
    recs = tr.records()
    assert len(recs) == 10
    assert tr.dropped() == 15
    assert recs[-1].name == "s24"          # newest survive


def test_record_span_external_interval():
    tr = Tracer()
    t0 = time.perf_counter()
    t1 = t0 + 0.5
    tr.record_span("queue_wait", t0, t1, trace_id="req-9", bucket=128)
    [r] = tr.records()
    assert r.duration_s == pytest.approx(0.5)
    assert r.trace_id == "req-9" and r.attrs == {"bucket": 128}
    assert r.parent_id is None


def test_exporters_jsonl_and_chrome(tmp_path):
    tr = Tracer()
    with tr.span("flush", items=2):
        with tr.span("prepare"):
            pass
    jl = str(tmp_path / "trace.jsonl")
    ch = str(tmp_path / "trace_chrome.json")
    assert tr.export_jsonl(jl) == 2
    assert tr.export_chrome_trace(ch) == 2
    lines = [json.loads(l) for l in open(jl)]
    assert {l["name"] for l in lines} == {"flush", "prepare"}
    for l in lines:
        assert l["t_end"] >= l["t_start"]
        assert l["t_wall_start"] > 1e9     # wall-clock re-anchored
    chrome = json.load(open(ch))
    evs = chrome["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert len(xs) == 2 and len(ms) >= 1   # spans + thread-name metadata
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0


def test_null_tracer_is_shared_noop(tmp_path):
    assert make_tracer(False) is NULL_TRACER
    assert isinstance(NULL_TRACER, NullTracer)
    # no allocation: every span is the same shared object
    assert NULL_TRACER.span("a", bucket=1) is _NULL_SPAN
    assert NULL_TRACER.span("b") is NULL_TRACER.span("c")
    with NULL_TRACER.span("x") as s:
        s.set(y=1)
    NULL_TRACER.record_span("z", 0.0, 1.0)
    assert NULL_TRACER.records() == []
    p = str(tmp_path / "empty.jsonl")
    assert NULL_TRACER.export_jsonl(p) == 0
    assert open(p).read() == ""


def test_disabled_span_overhead():
    """The disabled tracer must be decisively cheaper than a real span —
    the zero-cost-when-off contract for the serving hot path."""
    n = 20_000

    def loop(tracer):
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("hot", bucket=256):
                pass
        return time.perf_counter() - t0

    enabled = Tracer(max_spans=n)
    loop(NULL_TRACER), loop(enabled)       # warm both paths
    dt_off = min(loop(NULL_TRACER) for _ in range(3))
    dt_on = min(loop(enabled) for _ in range(3))
    assert dt_off < dt_on / 2, (dt_off, dt_on)


# ----------------------------------------------------------------- metrics

def test_counter_gauge_basics():
    c = Counter("n")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge("g")
    g.set(2.5)
    g.inc(0.5)
    assert g.value == 3.0


def test_histogram_stats_and_percentiles():
    h = Histogram("lat", buckets=default_latency_buckets())
    assert h.percentile(50) == 0.0         # empty: explicit zero, no fakery
    assert h.snapshot()["p50"] is None
    for v in (0.001, 0.002, 0.004, 0.008, 0.1):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(0.115)
    assert h.mean == pytest.approx(0.023)
    p50, p95 = h.percentile(50), h.percentile(95)
    assert 0.001 <= p50 <= p95 <= 0.1      # clamped to observed [min, max]
    snap = h.snapshot()
    assert snap["min"] == pytest.approx(0.001)
    assert snap["max"] == pytest.approx(0.1)


def test_histogram_single_observation_reports_itself():
    h = Histogram("x", buckets=(1.0, 2.0, 4.0))
    h.observe(3.3)
    for q in (0, 50, 95, 100):
        assert h.percentile(q) == pytest.approx(3.3)


def test_histogram_cumulative_buckets_monotone():
    h = Histogram("x", buckets=default_size_buckets(1, 64))
    for v in (1, 3, 3, 17, 1000):          # 1000 -> the +Inf bucket
        h.observe(v)
    cum = h.cumulative_buckets()
    counts = [c for _, c in cum]
    assert counts == sorted(counts)
    assert math.isinf(cum[-1][0]) and cum[-1][1] == 5


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("reqs")
    assert reg.counter("reqs") is c1
    with pytest.raises(TypeError):
        reg.gauge("reqs")
    h = reg.histogram("lat")
    assert reg.histogram("lat") is h


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", help="total requests").inc(3)
    reg.gauge("train_loss").set(0.25)
    h = reg.histogram("serve_latency_seconds", buckets=(0.1, 1.0),
                      help="latency")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.prometheus_text()
    lines = text.strip().split("\n")
    # every line is a comment or `name{labels} value`
    sample = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="[^"]+"\})? \S+$')
    for ln in lines:
        assert ln.startswith("# ") or sample.match(ln), ln
    assert "# TYPE serve_requests_total counter" in lines
    assert "# HELP serve_requests_total total requests" in lines
    assert "# TYPE serve_latency_seconds histogram" in lines
    assert 'serve_latency_seconds_bucket{le="0.1"} 1' in lines
    assert 'serve_latency_seconds_bucket{le="1.0"} 2' in lines
    assert 'serve_latency_seconds_bucket{le="+Inf"} 3' in lines
    assert "serve_latency_seconds_count 3" in lines
    assert any(l.startswith("serve_latency_seconds_sum ") for l in lines)
    assert "serve_requests_total 3.0" in lines


def test_snapshot_writer(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc(7)
    path = str(tmp_path / "metrics.json")
    w = SnapshotWriter(reg, path, interval_s=0.05).start()
    time.sleep(0.15)
    w.stop()                               # final snapshot on stop
    snap = json.load(open(path))
    assert snap["metrics"]["n"] == 7
    assert snap["time"] > 1e9
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_warn_once_dedups_per_key(caplog):
    log = logging.getLogger("test_warn_once")
    wo = warn_once(log)
    with caplog.at_level(logging.WARNING, logger="test_warn_once"):
        assert wo(("oversize", 512), "oversize 512") is True
        assert wo(("oversize", 512), "oversize 512") is False
        assert wo(("oversize", 1024), "oversize 1024") is True
    warned = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert len(warned) == 2
    assert wo.count(("oversize", 512)) == 2


# ---------------------------------------------------------------- bundle

def test_telemetry_bundle_disabled_is_null():
    tel = Telemetry.disabled()
    assert not tel.enabled
    assert tel.tracer is NULL_TRACER
    assert tel.span("x") is _NULL_SPAN
    # a scoped stage with nothing to record is the same shared no-op
    assert tel.span("region", scope="serve") is _NULL_SPAN
    with tel.span("region", scope="serve"):
        pass
    with tel.capture():                    # no trace_dir: no-op
        pass


def test_telemetry_bundle_export(tmp_path):
    tel = Telemetry(enabled=True, trace_dir=str(tmp_path))
    with tel.span("step", trace_id="step-0"):
        pass
    tel.metrics.counter("steps").inc()
    paths = tel.export()
    assert sorted(paths) == ["metrics_json", "metrics_prom", "trace_chrome",
                             "trace_jsonl"]
    [line] = [json.loads(l) for l in open(paths["trace_jsonl"])]
    assert line["name"] == "step" and line["trace_id"] == "step-0"
    assert json.load(open(paths["trace_chrome"]))["traceEvents"]
    assert "steps 1.0" in open(paths["metrics_prom"]).read()
    snap = json.load(open(paths["metrics_json"]))
    assert snap["metrics"]["steps"] == 1
    assert isinstance(snap["device_memory"], list)
    assert all("device" in d for d in snap["device_memory"])


def test_telemetry_from_config():
    from repro.configs.base import GNNConfig
    tel = Telemetry.from_config(GNNConfig())
    assert not tel.enabled
    tel = Telemetry.from_config(
        GNNConfig().replace(telemetry=True, trace_dir="/tmp/x"))
    assert tel.enabled and tel.trace_dir == "/tmp/x"

    class Legacy:                          # config predating the knobs
        pass
    assert not Telemetry.from_config(Legacy()).enabled


# ------------------------------------------------- ServerStats integration

def test_server_stats_report_schema_backward_compat():
    from repro.launch.serve_gnn import ServerStats
    stats = ServerStats()
    rep = stats.report()
    # the pre-telemetry schema, plus the new per-stage breakdown
    for key in ("requests", "p50_ms", "p95_ms", "mean_batch",
                "throughput_rps", "padding_waste_frac", "overflow_requests",
                "rejected_requests", "oversize_requests", "bucket_hits",
                "bucket_misses", "bucket_evictions", "bucket_compiles",
                "grown_buckets", "stages"):
        assert key in rep, key
    # empty: explicit zeros, not percentiles fabricated from fake samples
    assert rep["requests"] == 0
    assert rep["p50_ms"] == 0.0 and rep["p95_ms"] == 0.0
    assert rep["mean_batch"] == 0.0
    assert stats.latencies_s == [] and stats.batch_sizes == []

    stats.record_latency(0.010)
    stats.record_latency(0.020)
    stats.record_batch(2)
    stats.record_stage("prepare", 0.001)
    with stats.lock:
        stats.t_serving = 0.1
    rep = stats.report()
    assert rep["requests"] == 2
    assert 0.0 < rep["p50_ms"] <= rep["p95_ms"] <= 20.0 + 1e-6
    assert rep["mean_batch"] == 2.0
    assert rep["stages"]["prepare"]["count"] == 1
    assert stats.latencies_s == [0.010, 0.020]
    assert stats.batch_sizes == [2]


def test_server_stats_memory_bounded():
    """The memory-leak fix: unbounded traffic keeps O(1) state."""
    from repro.launch.serve_gnn import ServerStats
    stats = ServerStats(recent_cap=16)
    for i in range(10_000):
        stats.record_latency(i * 1e-6)
        stats.record_batch(1 + i % 4)
    assert len(stats.latencies_s) == 16    # recent window only
    assert len(stats.batch_sizes) == 16
    rep = stats.report()
    assert rep["requests"] == 10_000       # histogram saw everything
    assert rep["p95_ms"] >= rep["p50_ms"] > 0.0

    stats.reset()
    assert stats.report()["requests"] == 0
    assert stats.latencies_s == []


def test_server_telemetry_disabled_by_default():
    from repro.configs.base import GNNConfig
    from repro.launch.serve_gnn import GNNServer
    cfg = GNNConfig().reduced().replace(levels=(64, 128, 256))
    server = GNNServer(cfg, (128,), max_batch=2)
    assert not server.telemetry.enabled
    assert server.telemetry.tracer is NULL_TRACER
    # stats still stream into the (always-live) metrics registry
    assert server.stats.metrics is server.telemetry.metrics


def test_server_telemetry_end_to_end(tmp_path):
    """Background worker + concurrent submitters with telemetry on: spans
    cover the request lifecycle, stitch by trace_id across threads, stay
    well-nested per thread, and the artifacts export cleanly."""
    from repro.configs.base import GNNConfig
    from repro.data import geometry as geo
    from repro.launch.serve_gnn import GNNServer
    cfg = GNNConfig().reduced().replace(
        levels=(64, 128, 256), telemetry=True, trace_dir=str(tmp_path))
    server = GNNServer(cfg, (128,), max_batch=2, seed=0)
    assert server.telemetry.enabled
    verts, faces = geo.car_surface(geo.sample_params(0))

    server.start(deadline_s=0.01)
    ids, lock = [], threading.Lock()

    def client(k):
        for _ in range(3):
            rid = server.submit(verts, faces, 100 + 7 * k)
            with lock:
                ids.append(rid)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results = [server.result(rid, timeout=60) for rid in ids]
    server.stop()
    assert all(r.error is None for r in results)

    recs = server.telemetry.tracer.records()
    names = {r.name for r in recs}
    assert {"submit", "bucket_route", "queue_wait", "prepare", "dispatch",
            "device_wait", "harvest", "request", "result",
            "flush"} <= names, names
    assert check_well_nested(recs) == []
    # per-request stitching: every request's lifecycle shares one trace_id
    for rid in ids:
        tid = f"req-{rid}"
        stages = {r.name for r in recs if r.trace_id == tid}
        assert {"submit", "queue_wait", "request"} <= stages, (tid, stages)
    # lifecycle spans span threads: client-side submit, worker-side harvest
    t_names = {r.thread_name for r in recs}
    assert "gnn-serve-worker" in t_names and len(t_names) >= 2

    rep = server.stats.report()
    for stage in ("queue_wait", "prepare", "dispatch", "device_wait",
                  "harvest"):
        assert rep["stages"][stage]["count"] > 0, stage

    paths = server.telemetry.export()
    assert os.path.exists(paths["trace_jsonl"])
    spans = [json.loads(l) for l in open(paths["trace_jsonl"])]
    assert len(spans) == len(recs)
    chrome = json.load(open(paths["trace_chrome"]))
    assert len(chrome["traceEvents"]) > len(recs)   # + thread metadata
    prom = open(paths["metrics_prom"]).read()
    assert "serve_request_latency_seconds_count" in prom


# ------------------------------------------------ one stage, three sinks

class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""
    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw
        _FakeAnnotation.log.append(("new", name, kw))

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax
    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    return _FakeAnnotation.log


def test_span_one_clock_read_per_side_feeds_three_sinks(fake_annotation):
    tel = Telemetry(enabled=True)
    h = tel.metrics.histogram("serve_prepare_seconds")
    with tel.span("prepare", scope="serve", hist=h, bucket=256,
                  rids="3 4") as st:
        with tel.span("sample", scope="serve", rid=3):
            time.sleep(0.002)
    [inner, outer] = tel.tracer.records()
    assert (outer.name, inner.name) == ("prepare", "sample")
    assert inner.parent_id == outer.span_id
    # the span and the histogram carry the stage's own two clock reads
    assert (outer.t_start, outer.t_end) == (st.t0, st.t1)
    assert h.count == 1 and h.sum == outer.duration_s
    assert outer.attrs == {"bucket": 256, "rids": "3 4"}
    assert fake_annotation == [
        ("new", "serve/prepare", {"bucket": 256, "rids": "3 4"}),
        ("enter", "serve/prepare"),
        ("new", "serve/sample", {"rid": 3}), ("enter", "serve/sample"),
        ("exit", "serve/sample"), ("exit", "serve/prepare")]


def test_span_disabled_observes_without_annotation(fake_annotation):
    tel = Telemetry.disabled()
    h = tel.metrics.histogram("serve_prepare_seconds")
    with tel.span("prepare", scope="serve", hist=h, bucket=256) as st:
        pass
    assert h.count == 1 and h.sum == st.t1 - st.t0
    assert fake_annotation == []
    # without a scope an enabled span is a tracer span only
    on = Telemetry(enabled=True)
    with on.span("flush", items=2):
        pass
    assert fake_annotation == []
    assert [r.name for r in on.tracer.records()] == ["flush"]


def _stage_server(telemetry: bool, **kw):
    from repro.configs.base import GNNConfig
    from repro.launch.serve_gnn import GNNServer
    cfg = GNNConfig().reduced().replace(levels=(64, 128, 256),
                                        telemetry=telemetry)
    return GNNServer(cfg, (128,), max_batch=1, seed=0, **kw)


def test_server_stages_share_one_name_across_sinks(fake_annotation):
    """Every worker stage reaches the stats histogram, the tracer and the
    profiler annotation under one name; sample and check_cloud nest in
    prepare."""
    from repro.data import geometry as geo
    srv = _stage_server(True)
    verts, faces = geo.car_surface(geo.sample_params(0))
    srv.start(deadline_s=0.005)
    try:
        rids = [srv.submit(verts, faces, 128) for _ in range(2)]
        assert all(srv.result(r, timeout=120).error is None for r in rids)
    finally:
        srv.stop()
    stages = ("drain", "prepare", "sample", "check_cloud", "dispatch",
              "device_wait", "harvest", "publish")
    rep = srv.stats.report()["stages"]
    recs = srv.telemetry.tracer.records()
    names = {r.name for r in recs}
    opened = {name for ev, name, *_ in fake_annotation if ev == "new"}
    for s in stages:
        assert rep[s]["count"] > 0, s
        assert s in names, s
        assert f"serve/{s}" in opened, s
    assert "serve/call_b128" in opened
    by_id = {r.span_id: r for r in recs}
    for r in recs:
        if r.name in ("sample", "check_cloud"):
            assert by_id[r.parent_id].name == "prepare", r
    assert check_well_nested(recs) == []
    # annotation metadata: cheap scalars, the request ids as one string
    prep = [kw for ev, name, *kw in fake_annotation
            if ev == "new" and name == "serve/prepare"]
    assert all(set(kw[0]) == {"bucket", "batch", "rids"} for kw in prep)


def test_server_stages_build_no_annotation_when_off(fake_annotation):
    from repro.data import geometry as geo
    srv = _stage_server(False)
    verts, faces = geo.car_surface(geo.sample_params(0))
    srv.serve([(verts, faces, 128)] * 2)
    assert fake_annotation == []
    rep = srv.stats.report()["stages"]
    assert rep["prepare"]["count"] == 2 and rep["check_cloud"]["count"] == 2


def test_starved_counts_the_gap_between_synchronous_plans():
    from repro.data import geometry as geo
    srv = _stage_server(False, async_flush=False)
    srv.warmup()
    verts, faces = geo.car_surface(geo.sample_params(0))
    srv.serve([(verts, faces, 128)])
    assert srv.stats.report()["stages"]["starved"]["count"] == 0
    time.sleep(0.05)
    srv.serve([(verts, faces, 128)])
    st = srv.stats.report()["stages"]["starved"]
    assert st["count"] == 1 and st["total_s"] >= 0.05


def test_no_starved_between_items_of_one_async_plan():
    from repro.data import geometry as geo
    srv = _stage_server(False)
    srv.warmup()
    verts, faces = geo.car_surface(geo.sample_params(0))
    res = srv.serve([(verts, faces, 128)] * 3)     # one plan, three items
    assert len(res) == 3 and all(r.error is None for r in res)
    assert srv.stats.report()["stages"]["starved"]["count"] == 0


def test_late_dispatch_counted_when_previous_output_ready(monkeypatch):
    from repro.data import geometry as geo
    srv = _stage_server(False)
    srv.warmup()
    sample = srv._sample

    def slow_sample(req, n):        # the host outlasts the device
        time.sleep(0.3)
        return sample(req, n)

    monkeypatch.setattr(srv, "_sample", slow_sample)
    verts, faces = geo.car_surface(geo.sample_params(0))
    srv.serve([(verts, faces, 128)] * 2)
    rep = srv.stats.report()
    assert rep["late_dispatches"] == 1
    assert rep["stages"]["starved"]["count"] == 0
    prom = srv.stats.metrics.prometheus_text()
    assert "serve_late_dispatches_total 1.0" in prom

