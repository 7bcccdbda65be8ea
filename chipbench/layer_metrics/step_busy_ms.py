"""Union of chip 0's device-op intervals over the traced window, per
step."""
from chipbench import xplane

UNIT = "ms/step"
LAYER = "models"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    if trace is None:
        return None
    intervals, steps = trace.in_window(trace.devices[0])
    if not steps:
        return None
    return xplane.total(xplane.union(intervals)) / steps / 1e6
