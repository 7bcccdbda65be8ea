"""Least time the chip could take for the delta rule **as the recurrence
requires it** of a training step's Kimi Delta Attention layers, at its
published peaks, over ``kda_rule_ms``: the time of every device event
whose ``op_name`` holds ``/kda_rule/``, kernels and XLA operations alike
(chosen by scope and not as "every Pallas call", so that a plain body and
kernels read against the same work); in percent. The least time is counted
from the cell's shapes alone (``facts["kda"]``, the family's
``kda_rule_macs_per_step`` and ``kda_rule_bytes_per_step``: ``3 H d_h^2``
multiply-adds a position forward, again where ``remat`` recomputes, twice
backward; ``q``, ``k``, ``v``, ``g``, ``beta``, ``o`` and their gradients
once a pass). A chunked program executes several times the recurrence's
products and the scope also holds the gates and the L2 norms, so the share
is low, and honestly so. Left out where the program has no such scope."""
from chipbench import flops
from chipbench.layer_metrics import kda_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    shape = run.get("facts", {}).get("kda")
    spent = kda_ms.under(trace, (kda_ms.RULE,))
    if not spent or not shape or run.get("peak") is None:
        return None
    seconds, bound = flops.roofline_seconds(
        2.0 * shape["rule_macs_per_step"], shape["rule_bytes_per_step"],
        run["peak"])
    print(f"kda_rule_roofline: bound by {bound}; least {1e3 * seconds:.6f} "
          f"ms over {spent:.6f} ms a step", flush=True)
    return 100.0 * 1e3 * seconds / spent
