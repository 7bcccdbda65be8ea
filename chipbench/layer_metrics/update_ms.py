"""Time chip 0 spends a step in the package's own work that is not a
collective: operations under ``hvt_reduce_gradients`` (divides, casts,
scaling) and under ``hvt_optimizer_update`` (the wrapped optimizer).
The update's alone: where XLA fuses the optimizer's arithmetic into the
matrix multiplication that makes the gradient, that fusion is ``bwd_ms``'
(``chipbench/regions.py``, and ``look`` there for how much). Left out
where the program has no such scope."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "gradient path"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    return regions.read(trace, ("reduce", "update"), needs_scopes=True)
