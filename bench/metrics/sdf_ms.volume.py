"""sdf_ms.volume: device time under ``graphx/volume_features`` (the band
search, the prefix fallback and the per-slab features) per geometry, over
the program executions wholly inside the traced span (device trace)."""
from bench import tracing


def read(ctx):
    t = ctx["trace"]
    sdf = tracing.scope_seconds(t, "graphx/volume_features") if t else None
    if not sdf:
        return None
    return sdf / (t["executions"] * ctx["run"]["rows_per_execution"]) * 1e3
