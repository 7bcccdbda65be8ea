"""Time chip 0 spends a step in operations of the backward pass: those
whose name stack (``chipbench/regions.py``) holds ``transpose(jvp(`` and
not ``rematted_computation``."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    return regions.read(trace, ("backward",))
