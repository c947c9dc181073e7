"""check_cloud_ms.sweep: host time of the cloud check (grid overflow
counts at every level) per request served (ServerStats ``check_cloud`` sum
over requests, window only)."""


def read(ctx):
    stages = ctx["run"]["stages"]
    served = stages["requests"][0]
    if not served or "check_cloud" not in stages:
        return None
    return stages["check_cloud"][1] / served * 1e3
