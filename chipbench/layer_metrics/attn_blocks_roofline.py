"""Least time the chip could take for **the products over positions a
block-diffusion training step requires** (a clean and a noised copy of
every sequence under the block-causal rule), at its published peaks, over
the time of the **Pallas kernel calls whose ``op_name`` holds
``/attn_blocks/``** (the forward, the recomputed forward and the one
backward kernel of both walks of every such layer); in percent. The calls
are chosen by scope and not as "every Pallas call": the program's expert
layers and its rotary run kernels of their own. The least time is counted
from the cell's shapes alone (``facts["attn_blocks"]``, the family's
``attn_blocks_macs_per_step`` and the bytes of two calls a layer: the exact
``B^2 n (n + 1) / 2 + B^2 n (n - 1) / 2 + L B`` pairs a head and sequence,
the last layer's clean rows left out since the loss reads nothing of them,
two products a pair in the forward pass and in its recomputation, five in
the backward; q, k, v, o, their gradients and the two statistics moved once
a call), so it counts the same work whatever implements it: a program that
walks every tile of ``2 L x 2 L`` and masks reads about a quarter of what
one that walks the causal tiles of each copy does, and nothing can read
over 100%. The ``L B`` pairs of a block on itself are in the count and
their time is not (XLA makes them: ``attn_blocks_merge_ms``): 0.05% of the
pairs at 8,192 positions in blocks of 4. Left out where the program has no
such kernel."""
from chipbench import flops
from chipbench.layer_metrics.attn_blocks_core_ms import under_the_scope_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    shape = run.get("facts", {}).get("attn_blocks")
    spent = under_the_scope_ms(trace, run, kernels=True)
    if not spent or not shape or run.get("peak") is None:
        return None
    seconds, bound = flops.roofline_seconds(
        2.0 * shape["macs_per_step"], shape["bytes_per_step"], run["peak"])
    print(f"attn_blocks_roofline: bound by {bound}; least "
          f"{1e3 * seconds:.6f} ms over {spent:.6f} ms a step", flush=True)
    return 100.0 * 1e3 * seconds / spent
