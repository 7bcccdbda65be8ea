"""Least time the chip could take for **the products a training step
requires over the chosen pairs** of its sparse-attention layers, at its
published peaks, over the time of every event whose ``op_name`` holds
``/dsa_core/`` (``dsa_core_ms``: selected by scope and not as "every
Pallas call", this program's other layers run Pallas kernels of their
own); in percent. The least time is counted from the cell's shapes alone
(``facts["dsa"]``, the family's ``dsa_core_macs_per_step`` and
``dsa_core_bytes_per_step``: two products a chosen pair in the forward
pass and in its recomputation, five in the backward, ``sum_t min(t + 1,
topk)`` pairs a head), so it counts the same work whatever implements it:
a program that walks every causal tile and masks reads about a quarter of
what one that skips would, and nothing can read over 100%. Left out where
the program has no such scope."""
from chipbench import flops
from chipbench.layer_metrics import dsa_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    shape = run.get("facts", {}).get("dsa")
    spent = dsa_ms.under(trace, (dsa_ms.CORE,))
    if not spent or not shape or run.get("peak") is None:
        return None
    seconds, bound = flops.roofline_seconds(
        2.0 * shape["core_macs_per_step"], shape["core_bytes_per_step"],
        run["peak"])
    print(f"dsa_core_roofline: bound by {bound}; least {1e3 * seconds:.6f} "
          f"ms over {spent:.6f} ms a step", flush=True)
    return 100.0 * 1e3 * seconds / spent
