"""mp_roofline.sweep: the message-passing layers' least time per geometry
(operations and bytes of the real nodes and edges, bench/counts.py, over the
chip's peaks) as a share of their device time per geometry under
``mgn/message_passing`` (device trace)."""
from bench import counts, tracing


def read(ctx):
    t, g = ctx["trace"], ctx["run"]["graph"]
    mp = tracing.scope_seconds(t, "mgn/message_passing") if t else None
    if not mp or not g["edges"]:
        return None
    cfg = ctx["config"]
    least = counts.least_seconds(
        counts.mp_flops(cfg, g["nodes"], g["edges"]),
        counts.mp_bytes(cfg, g["nodes"], g["edges"]), ctx["peaks"])
    rows = t["executions"] * ctx["run"]["rows_per_execution"]
    return 100.0 * least / (mp / rows)
