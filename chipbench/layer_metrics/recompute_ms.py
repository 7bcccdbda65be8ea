"""Time chip 0 spends a step running forward operations again under
``remat``: those whose name stack (``chipbench/regions.py``) holds
``rematted_computation``. 0 for a model built without ``remat``."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    return regions.read(trace, ("recompute",))
