"""Least time the chip could take for **the products a training step
requires over the band** of its windowed attention layers (a query sees
the last ``window`` causal keys), at its published peaks, over the time of
the **Pallas kernel calls whose ``op_name`` holds ``/attn_window/``** (the
forward, the recomputed forward and the one backward kernel of every such
layer); in percent. The calls are chosen by scope and not as "every Pallas
call": the program's full layers run the same kernels, and its expert
layers and router kernels of their own. The least time is counted from
the cell's shapes alone (``facts["attn_window"]``, the family's
``attn_window_macs_per_step`` and ``attn_window_bytes_per_step``: the
exact ``w (w + 1) / 2 + (s - w) w`` pairs a head and sequence, two
products a pair in the forward pass and in its recomputation, five in the
backward; q, k, v, o, their gradients and the two statistics moved once a
call), so it counts the same work whatever implements it: a program that
walks every causal tile and masks reads about a quarter of what one that
skips would, and nothing can read over 100%. What XLA puts around the
kernels is in ``attn_window_core_ms`` and not here. Left out where the
program has no such kernel."""
from chipbench import flops, kernel_calls
from chipbench.layer_metrics.attn_window_core_ms import SCOPE

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def kernels_ms(trace, run):
    """ms a step of chip 0's Pallas calls under ``attn_window``; None
    where there is no trace, no program in it, no window or no call."""
    found = kernel_calls.window(trace, run)
    if found is None:
        return None
    return sum(e.ns for e in found.events
               if e.kernel and SCOPE in e.part) / found.steps / 1e6 or None


def read(trace, run):
    shape = run.get("facts", {}).get("attn_window")
    spent = kernels_ms(trace, run)
    if not spent or not shape or run.get("peak") is None:
        return None
    seconds, bound = flops.roofline_seconds(
        2.0 * shape["macs_per_step"], shape["bytes_per_step"], run["peak"])
    print(f"attn_window_roofline: bound by {bound}; least "
          f"{1e3 * seconds:.6f} ms over {spent:.6f} ms a step", flush=True)
    return 100.0 * 1e3 * seconds / spent
