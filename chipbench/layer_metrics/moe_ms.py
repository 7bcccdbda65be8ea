"""Time chip 0 spends a step in the expert layers
(``horovod_tpu/models/moe.py``): every operation under one of its four
scopes ``moe_route``, ``moe_dispatch``, ``moe_experts`` and
``moe_combine``, forward, recomputed and backward together, so it
overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by construction.
``moe_experts_ms + moe_shuffle_ms``. Left out where the program has no
such scope."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

PASSES = ("forward", "recompute", "backward")
EXPERTS = "/moe_experts/"
SHUFFLE = ("/moe_route/", "/moe_dispatch/", "/moe_combine/")


def under(trace, scopes):
    """ms a step under any of ``scopes`` (an operation's naming part
    holds at most one of them); None where nothing is."""
    found = [regions.read(trace, PASSES, scope=s) for s in scopes]
    return sum(filter(None, found)) or None


def read(trace, run):
    return under(trace, (EXPERTS, *SHUFFLE))
