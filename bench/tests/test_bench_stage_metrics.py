"""The readers of the serving worker's own stages (``starved_ms.sweep``,
``check_cloud_ms.sweep``) on synthetic runs, and the server's public handle
on its compiled program as the source of the trace's op names."""
import pytest

from bench import run, tracing


def ctx_with(stages):
    return {"run": {"stages": stages}}


@pytest.mark.parametrize("metric,stage", [("starved_ms.sweep", "starved"),
                                          ("check_cloud_ms.sweep",
                                           "check_cloud")])
def test_stage_reader_per_request_ms(metric, stage):
    read = run.load_module("metrics", f"{metric}.py").read
    stages = {"requests": (8, 0.0), stage: (3, 0.4), "prepare": (8, 1.0)}
    assert read(ctx_with(stages)) == pytest.approx(50.0)
    # a stage that never fired in the window reads 0, not nothing
    assert read(ctx_with(dict(stages, **{stage: (0, 0.0)}))) == 0.0
    # nothing served: nothing to read
    assert read(ctx_with(dict(stages, requests=(0, 0.0)))) is None
    # a program without the stage (an older server): nothing to read
    absent = {k: v for k, v in stages.items() if k != stage}
    assert read(ctx_with(absent)) is None


def test_compiled_handle_names_scopes_like_private_route():
    from repro.configs.base import GNNConfig
    from repro.launch.serve_gnn import GNNServer
    cfg = GNNConfig().reduced().replace(levels=(64, 128, 256))
    srv = GNNServer(cfg, (128,), max_batch=1, seed=0)
    srv.warmup()
    names = tracing.hlo_op_names(srv.compiled(128).as_text())
    paths = set(names.values())
    assert any("graphx/knn_edges" in p for p in paths)
    assert any("mgn/message_passing" in p for p in paths)
    private = tracing.hlo_op_names(srv._buckets[128].infer.lower(
        *srv._bucket_arg_specs(128)).compile().as_text())
    assert names == private
    with pytest.raises(KeyError):
        srv.compiled(4096)
