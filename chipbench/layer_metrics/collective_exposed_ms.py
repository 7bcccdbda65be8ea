"""The part of ``collective_ms`` during which no compute operation (nor
kernel) runs on chip 0, per step."""
from chipbench import xplane

UNIT = "ms/step"
LAYER = "gradient path"
MOVES = "tok_s_chip"


def read(trace, run):
    if trace is None:
        return None
    device = trace.devices[0]
    collective, steps = trace.in_window(device, "collective")
    if not steps:
        return None
    work = (trace.in_window(device, "compute")[0]
            + trace.in_window(device, "kernel")[0])
    return xplane.total(xplane.subtract(collective, work)) / steps / 1e6
