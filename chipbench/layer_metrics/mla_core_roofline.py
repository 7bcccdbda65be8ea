"""Least time the chip could take for the products over positions a
training step requires of its latent-attention layers, at its published
peaks, over the time of the **Pallas kernel calls whose ``op_name`` holds
``/mla_core/``** (the forward, the recomputed forward and the one
backward kernel of every layer); in percent. The calls are chosen by
scope and not as "every Pallas call": this program's expert layers and
router run Pallas kernels of their own. The least time is counted from
the cell's shapes alone (``facts["mla"]``, the family's
``mla_core_macs_per_step`` and ``mla_core_bytes_per_step``: exact causal
pairs at the query-key width and the value width, not tiles and not a
padded width); the trace gives only the time spent, so a program that
pads a width or assembles the key another way reads against the same
work. What XLA puts around the kernels is in ``mla_core_ms`` and not
here. Left out where the program has no such kernel."""
from chipbench import flops, regions, xplane
from chipbench.layer_metrics import mla_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def kernels_ms(trace):
    """ms a step of chip 0's Pallas calls under ``mla_core``; None where
    there is no trace, no program in it or no window."""
    path = regions.trace_file() if trace is not None else None
    names = regions.name_stacks(path) if path else None
    window = trace.window(trace.devices[0]) if names else None
    if window is None:
        return None
    lo, hi, steps = window
    return sum((b - a) / steps / 1e6
               for op in trace.devices[0].ops if op.kind == "kernel"
               and mla_ms.CORE in names.get(op.name, "")
               for a, b in xplane.clip([(op.start, op.end)], lo, hi))


def read(trace, run):
    shape = run.get("facts", {}).get("mla")
    spent = kernels_ms(trace)
    if not spent or not shape or run.get("peak") is None:
        return None
    seconds, bound = flops.roofline_seconds(
        2.0 * shape["core_macs_per_step"], shape["core_bytes_per_step"],
        run["peak"])
    print(f"mla_core_roofline: bound by {bound}; least {1e3 * seconds:.6f} "
          f"ms over {spent:.6f} ms a step", flush=True)
    return 100.0 * 1e3 * seconds / spent
