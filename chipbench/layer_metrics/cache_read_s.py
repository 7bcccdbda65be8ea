"""Seconds reading and loading executables from the persistent cache
during set-up (``cache_retrieval_time_sec``); ``compile_s`` less this is
what was compiled anew (``chipbench/startup_split.py``)."""
from chipbench import startup_split

UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"


def read(trace, run):
    return startup_split.read("cache_read_s", run)
