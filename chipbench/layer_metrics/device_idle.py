"""Share of the traced window in which no operation ran on the device,
averaged over the chips: what the host adds."""
from chipbench import xplane

UNIT = "%"
LAYER = "device"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    if trace is None:
        return None
    found = xplane.busy_and_window_seconds(trace)
    if found is None:
        return None
    busy_s, window_s = found
    return 100.0 * (1.0 - busy_s / window_s)
