"""Seconds inside ``backend_compile`` during set-up, cache reads
included (``setup_sources.CompileMeter``)."""
UNIT = "s"
LAYER = "entry points"
MOVES = "setup_s"


def read(trace, run):
    return run["compile_seconds"]
