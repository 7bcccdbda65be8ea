"""Time chip 0 spends a step in operations of the forward pass: those
whose name stack (``chipbench/regions.py``) holds ``jvp(`` and neither
``transpose(jvp(`` nor ``rematted_computation``. The loss is part of it."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    return regions.read(trace, ("forward",))
