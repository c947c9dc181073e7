"""knn_ms.sweep: device time under ``graphx/knn_edges`` (hash grid, top-k,
multi-scale union) per geometry, over the program executions wholly inside
the traced span (device trace)."""
from bench import tracing


def read(ctx):
    t = ctx["trace"]
    knn = tracing.scope_seconds(t, "graphx/knn_edges") if t else None
    if not knn:
        return None
    return knn / (t["executions"] * ctx["run"]["rows_per_execution"]) * 1e3
