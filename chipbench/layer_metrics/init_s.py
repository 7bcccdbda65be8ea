"""The whole of ``hvt.init()``: span ``hvt_startup/init`` of the package's
recorder, within set-up (``chipbench/startup_split.py``)."""
from chipbench import startup_split

UNIT = "s"
LAYER = "topology"
MOVES = "setup_s"


def read(trace, run):
    return startup_split.read("init_s", run)
