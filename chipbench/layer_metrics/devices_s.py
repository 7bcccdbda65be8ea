"""The process's first ``jax.devices()``, span ``hvt_startup/devices``
inside ``hvt_startup/init``: the runtime's bring-up, not the package
(``chipbench/startup_split.py``)."""
from chipbench import startup_split

UNIT = "s"
LAYER = "topology"
MOVES = "setup_s"


def read(trace, run):
    return startup_split.read("devices_s", run)
