"""mfu.sweep: the forward pass's operations per geometry (real nodes and
edges, bench/counts.py) times geometries answered per second in the traced
run's window, over the chip's peak (host clock and counts)."""
from bench import counts


def read(ctx):
    g, rate = ctx["run"]["graph"], ctx["run"].get("rate")
    if not rate or not g["edges"]:
        return None
    flops = counts.forward_flops(ctx["config"], g["nodes"], g["edges"])
    return 100.0 * flops * rate / ctx["peaks"]["flops_per_s"]
