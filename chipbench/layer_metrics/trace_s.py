"""Seconds JAX spent tracing during set-up, nested spans counted once
and a second under two stages given to the inner one
(``jaxpr_trace_duration``): the Python of ``models/``, ``ops/`` and
``jax/__init__.py`` (``chipbench/startup_split.py``)."""
from chipbench import startup_split

UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"


def read(trace, run):
    return startup_split.read("trace_s", run)
