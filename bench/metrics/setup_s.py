"""setup_s: seconds from process start to the start of the window (host
clock): imports, weights, geometry pool, server, compile or cache load,
warm burst."""


def read(ctx):
    return ctx["run"]["setup_s"]
