"""Time chip 0 spends a step in operations no region claims
(``chipbench/regions.py``): parameter copies, ``optax.apply_updates``
where XLA did not fuse it with the optimizer, whatever XLA left unnamed.
Left out where the program does not name its optimizer, which would
then be most of it."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "device"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    return regions.read(trace, ("unattributed",), needs_scopes=True)
