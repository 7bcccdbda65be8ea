"""Seconds JAX spent lowering traced programs to MLIR during set-up,
counted as ``trace_s`` is (``jaxpr_to_mlir_module_duration``;
``chipbench/startup_split.py``)."""
from chipbench import startup_split

UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"


def read(trace, run):
    return startup_split.read("lower_s", run)
