"""Model FLOP/s over the chips' published bf16 peak, in percent: the
family's required FLOPs an item (``chipbench/flops.py``; recomputation
not counted) times items per second per chip over one chip's peak."""
UNIT = "%"


def read(trace, run):
    if run["peak"] is None:
        return None
    return (100.0 * run["flops_per_item"] * run["items_per_s_chip"]
            / run["peak"]["bf16_flops_per_s"])
