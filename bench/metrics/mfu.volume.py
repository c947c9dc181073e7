"""mfu.volume: one full-domain forward pass's operations (every voxel
once, bench/counts_unet.py) times the run's geometries per second (whole
service periods, bench/cells/volume.py), over the chip's peak (host clock
and counts)."""
from bench import counts_unet


def read(ctx):
    rate = ctx["run"].get("rate")
    if not rate:
        return None
    flops = counts_unet.domain_flops(ctx["config"])
    return 100.0 * flops * rate / ctx["peaks"]["flops_per_s"]
