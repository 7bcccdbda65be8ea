"""``setup_seconds`` less the package's import, ``hvt.init()`` and
what JAX spent tracing, lowering, compiling and reading its cache: what
no span of the program explains (the harness's own imports, the
weights' first execution, the checks before, the two warm-up steps;
``chipbench/startup_split.py``)."""
from chipbench import startup_split

UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"


def read(trace, run):
    return startup_split.read("setup_unnamed_s", run)
