"""unet_roofline.volume: the U-Net's least time per geometry over every
slab window the program computes (operations and bytes from
bench/counts_unet.py, over the chip's peaks) as a share of its device time
per geometry under ``unet/`` (device trace)."""
from bench import counts, counts_unet, tracing


def read(ctx):
    t = ctx["trace"]
    unet = tracing.scope_seconds(t, "unet") if t else None
    if not unet:
        return None
    cfg = ctx["config"]
    least = counts.least_seconds(counts_unet.served_flops(cfg),
                                 counts_unet.served_bytes(cfg), ctx["peaks"])
    rows = t["executions"] * ctx["run"]["rows_per_execution"]
    return 100.0 * least / (unet / rows)
