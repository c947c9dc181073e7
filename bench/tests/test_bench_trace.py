"""Trace reduction: busy union, time by named-scope path over whole
executions, idle gaps labelled by host annotations; on a hand-built trace
and on a small trace recorded on a v5e."""
import os

import pytest

from bench import run, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")
O = tracing._Obj


def ev(name, start, dur, **stats):
    return O(name=name, start_ns=start, duration_ns=dur,
             stats=list(stats.items()))


def hand_trace():
    # traced span 0..1000 ns; two executions of the main program (100..400,
    # 500..900) and one of another (950..1000, cut by the span's end)
    host = O(name="/host:CPU", lines=[
        O(name="python", events=[ev("bench/traced", 0, 1000),
                                 ev("bench/wait_result", 0, 1000)]),
        O(name="worker", events=[ev("serve/dispatch", 420, 60)])])
    mods = [ev("jit_base", 100, 300), ev("jit_base", 500, 400),
            ev("jit_other", 950, 100)]
    ops = [ev("fusion.1", 100, 100, tf_op="jit(base)/graphx/knn_edges/x"),
           ev("fusion.2", 150, 100,         # overlaps fusion.1
              tf_op="jit(base)/mgn/message_passing/mgn/aggregate/y"),
           ev("fusion.3", 300, 100, tf_op="jit(base)/mgn/decode/z"),
           ev("fusion.2", 500, 200,
              tf_op="jit(base)/mgn/message_passing/mgn/aggregate/y"),
           ev("copy.9", 700, 200),
           ev("fusion.7", 950, 100, tf_op="jit(other)/graphx/knn_edges/x")]
    dev = O(name="/device:TPU:0", lines=[O(name="XLA Modules", events=mods),
                                         O(name="XLA Ops", events=ops)])
    return O(planes=[host, dev])


def test_reduction_of_a_hand_built_trace():
    r = tracing.reduce_trace(hand_trace())
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: 100..250, 300..400, 500..900, 950..1000 (clipped)
    assert r["busy_s"] == pytest.approx(700e-9)
    assert r["executions"] == 2
    # only ops inside whole executions of the main program count
    scope = lambda s: tracing.scope_seconds(r, s)
    assert scope("graphx/knn_edges") == pytest.approx(100e-9)
    assert scope("mgn/message_passing") == pytest.approx(300e-9)
    assert scope("mgn/aggregate") == pytest.approx(300e-9)
    assert scope("mgn/decode") == pytest.approx(100e-9)
    assert scope("mgn/encode") is None
    ops = dict(r["device_ops"])
    assert ops["graphx/knn_edges:fusion.1"] == pytest.approx(100e-9)
    assert ops["graphx/knn_edges:fusion.7"] == pytest.approx(50e-9)
    assert ops["mgn/message_passing/mgn/aggregate:fusion.2"] == \
        pytest.approx(300e-9)
    assert ops["other:copy.9"] == pytest.approx(200e-9)
    gaps = dict(r["idle_gaps"])
    # 0..100, 250..300 and 900..950 under bench/wait_result only; 400..500
    # with the server's dispatch open too
    assert gaps["bench/wait_result"] == pytest.approx(200e-9)
    assert gaps["bench/wait_result+serve/dispatch"] == \
        pytest.approx(100e-9)


def test_reduction_needs_the_span_mark():
    t = hand_trace()
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tracing.reduce_trace(t)


def test_scope_paths_drop_transformation_wrappers():
    assert tracing.scope_path(
        "jit(infer)/vmap(graphx/knn_edges)/jit(take_along_axis)/gather") \
        == "infer/graphx/knn_edges/take_along_axis/gather"
    assert tracing.scope_path("jit(f)/vmap()/transpose(jvp(mgn))/dot") == \
        "f/mgn/dot"
    r = {"path_s": {"f/mgn/message_passing/add": 2.0,
                    "f/mgn/message/add": 1.0,
                    "f/graphx/model/mgn/message_passing/dot": 3.0}}
    # a scope matches whole segments, wherever it sits in the path
    assert tracing.scope_seconds(r, "mgn/message_passing") == 5.0
    assert tracing.scope_seconds(r, "mgn/message") == 1.0
    assert tracing.scope_seconds(r, "message_pass") is None


def test_a_trace_that_cannot_feed_the_metrics_fails_the_run(monkeypatch):
    t = hand_trace()
    monkeypatch.setattr(tracing, "load", lambda _: t)
    assert run.reduce_trace({"op_names": {}})["executions"] == 2
    # no whole execution inside the span
    t.planes[1].lines[0].events[:] = [ev("jit_base", 0, 2000)]
    with pytest.raises(RuntimeError, match="no whole program execution"):
        run.reduce_trace({"op_names": {}})
    # no op under any named scope
    t = hand_trace()
    for e in t.planes[1].lines[1].events:
        e.stats = []
    with pytest.raises(RuntimeError, match="named scope"):
        run.reduce_trace({"op_names": {}})


def test_scopes_from_the_compiled_programs_op_names():
    hlo = ('  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'calls=%fc, metadata={op_name="jit(infer)/vmap(graphx/model)/'
           'mgn/message_passing/mgn/aggregate/scatter-add" '
           'source_file="x.py"}\n'
           '  ROOT %copy.1 = f32[8]{0} copy(%fusion.3), '
           'metadata={op_name="jit(infer)/vmap(graphx/knn_edges)/sort"}\n'
           '  %param.0 = f32[8]{0} parameter(0)\n')
    names = tracing.hlo_op_names(hlo)
    assert set(names) == {"fusion.3", "copy.1"}
    t = hand_trace()
    ops = t.planes[1].lines[1].events
    ops[4] = ev("copy.1", 700, 200)          # no stats of its own
    r = tracing.reduce_trace(t, names)
    assert tracing.scope_seconds(r, "graphx/knn_edges") == \
        pytest.approx(300e-9)


def test_tpu_op_events_named_by_their_hlo_text():
    # on a TPU an op event's name is the instruction's text, with no scope
    # stat; the scope comes from the compiled program's op names, and a
    # while loop holding other ops is busy time but not an op of its own
    t = hand_trace()
    ops = t.planes[1].lines[1].events
    ops[4] = ev("%fusion.9 = f32[8]{0:T(8,128)} fusion(f32[8]{0} %p.1), "
                "kind=kLoop", 700, 200, device_duration_ps=200000)
    ops.append(ev("%while.2 = (s32[]{:T(128)}, f32[1,8]{1,0:T(8,128)}) "
                  "while((s32[], f32[1,8]) %tuple.1)", 500, 400))
    names = {"fusion.9": "jit(infer)/vmap(graphx/model)/while/body/"
                         "mgn/message_passing/dot_general",
             "while.2": "jit(infer)/vmap(graphx/model)/while"}
    r = tracing.reduce_trace(t, names)
    assert tracing.scope_seconds(r, "mgn/message_passing") == \
        pytest.approx(500e-9)
    ops_ = dict(r["device_ops"])
    assert ops_["graphx/model/mgn/message_passing:fusion.9"] == \
        pytest.approx(200e-9)
    assert not any("while" in k for k in ops_)
    assert r["busy_s"] == pytest.approx(700e-9)


def test_reduction_of_a_trace_recorded_on_a_v5e():
    # 2.6 s of a traced mgn-serve-sweep run on one v5e: op events named by
    # their HLO instruction, host annotations, and the op-name map of the
    # compiled bucket program (tracing.hlo_op_names)
    import gzip
    import json
    path = os.path.join(DATA, "v5e_mgn_sweep_trace.json.gz")
    with gzip.open(path, "rt") as f:
        names = json.load(f)["op_names"]
    r = tracing.reduce_trace(tracing.load_json(path), names)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(2.6)
    assert 0.9 < r["busy_s"] / r["window_s"] <= 1.0
    assert r["executions"] == 2
    knn = tracing.scope_seconds(r, "graphx/knn_edges")
    mp = tracing.scope_seconds(r, "mgn/message_passing")
    # at hidden 128 the graph build takes more device time than the layers
    assert knn > mp > 0 and knn + mp <= r["busy_s"]
    assert not any(":while" in name for name, _ in r["device_ops"])
    assert r["idle_gaps"][0][0].startswith("bench/")
