"""serve_geoms_per_s: geometries answered per second in a closed loop, over
the whole window, which starts and ends at an answer (host clock)."""


def read(ctx):
    return ctx["run"].get("rate")
