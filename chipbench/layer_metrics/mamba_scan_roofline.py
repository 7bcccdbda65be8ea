"""Least time the chip could take for the selective scan **as the
recurrence requires it** of a training step's Mamba-1 layers, at its
published peaks, over ``mamba_scan_ms``: the time of every device event
whose ``op_name`` holds ``/mamba_scan/``, kernels and XLA operations alike
(chosen by scope and not as "every Pallas call", so that a plain body and
kernels read against the same work); in percent. The least time is counted
from the cell's shapes alone (``facts["mamba"]``, the family's
``mamba_scan_macs_per_step`` and ``mamba_scan_bytes_per_step``: four
multiply-adds a position, channel and state forward, again where ``remat``
recomputes, twice backward; ``u``, ``delta``, ``B``, ``C``, ``m`` and their
gradients once a pass). The recurrence makes no product on the MXU and is
bound by its bytes, which are few beside the ``[chunk, D, N]`` a plain body
moves, so the share is low, and honestly so. Left out where the program has
no such scope."""
from chipbench import flops
from chipbench.layer_metrics import mamba_scan_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    shape = run.get("facts", {}).get("mamba")
    spent = mamba_scan_ms.read(trace, run)
    if not spent or not shape or run.get("peak") is None:
        return None
    seconds, bound = flops.roofline_seconds(
        2.0 * shape["scan_macs_per_step"], shape["scan_bytes_per_step"],
        run["peak"])
    print(f"mamba_scan_roofline: bound by {bound}; least "
          f"{1e3 * seconds:.6f} ms over {spent:.6f} ms a step", flush=True)
    return 100.0 * 1e3 * seconds / spent
