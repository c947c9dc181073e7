"""prepare_ms.sweep: host prepare time (surface sampling and the
cloud check) per request served (ServerStats ``prepare`` sum over requests,
window only)."""


def read(ctx):
    stages = ctx["run"]["stages"]
    served = stages["requests"][0]
    if not served:
        return None
    return stages.get("prepare", (0, 0.0))[1] / served * 1e3
