"""starved_ms.sweep: device-starved time per request served (ServerStats
``starved`` sum over requests, window only): from the end of a device wait
that left the server with no execution in flight to its next program call.
A lower bound where ``late_dispatches`` counts calls that found the device
already done."""


def read(ctx):
    stages = ctx["run"]["stages"]
    served = stages["requests"][0]
    if not served or "starved" not in stages:
        return None
    return stages["starved"][1] / served * 1e3
