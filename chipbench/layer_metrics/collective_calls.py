"""Collective instructions chip 0 runs a step (an asynchronous pair is
one), counted as ``collective_mb`` counts them."""
from chipbench.layer_metrics import collective_mb

UNIT = "calls/step"
LAYER = "gradient path"
MOVES = "tok_s_chip"


def read(trace, run):
    found = collective_mb.calls_and_bytes(trace)
    return None if found is None else found[0]
