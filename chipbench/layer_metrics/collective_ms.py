"""Time chip 0's core spends inside collective instructions (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all; for an
asynchronous pair the ``-start`` and ``-done`` instructions themselves,
not the span between them) over the traced window, per step."""
from chipbench import xplane

UNIT = "ms/step"
LAYER = "gradient path"
MOVES = "tok_s_chip"


def read(trace, run):
    if trace is None:
        return None
    intervals, steps = trace.in_window(trace.devices[0], "collective")
    if not steps:
        return None
    return xplane.total(xplane.union(intervals)) / steps / 1e6
