"""Summed durations of the Pallas custom calls on chip 0 over the traced
window, per step. Reads 0 where the program's own rule picks the einsum
path."""
from chipbench import xplane

UNIT = "ms/step"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    if trace is None:
        return None
    intervals, steps = trace.in_window(trace.devices[0], "kernel")
    if not steps:
        return None
    return xplane.total(intervals) / steps / 1e6
