"""Least time the chip could take for the grouped products a training
step has to make in its expert layers, at its published peaks, over
``moe_experts_ms``: all the time under the scope ``moe_experts``, the
weight casts and the activation included; in percent. The least time is
counted from the cell's shapes alone (a layer: three products forward,
three more where ``remat`` runs it again, six in its backward pass);
the trace gives only the time spent, so a program that fuses or splits
its products reads against the same work."""
from chipbench import flops
from chipbench.layer_metrics import moe_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def product_flops(rows: int, d_model: int, d_expert: int) -> float:
    """One grouped product: every row times one ``d_model x d_expert``
    matrix (gate and up), or its transpose (down, and the backward
    pass's products, which contract over another axis of the same three
    sizes)."""
    return 2.0 * rows * d_model * d_expert


def product_bytes(rows: int, experts: int, d_model: int, d_expert: int,
                  itemsize: int) -> float:
    """What one product has to move once: the rows on one side
    (``rows x d_model``), the rows on the other (``rows x d_expert``)
    and one stack of expert matrices, whichever two it reads and
    whichever it writes."""
    return float(itemsize) * (rows * (d_model + d_expert)
                              + experts * d_model * d_expert)


def products_a_step(layers: int, remat: bool) -> int:
    """A layer's forward pass is gate, up and down; its backward pass
    twice that (each product's gradient by its rows and by its
    matrices); under ``remat`` the forward pass runs a second time."""
    return layers * (12 if remat else 9)


def least_ms(facts: dict, peak: dict):
    """``(ms a step, which bound)`` from the family's ``facts``."""
    shape = facts["moe"]
    seconds, bound = flops.roofline_seconds(
        product_flops(shape["rows"], shape["d_model"], shape["d_expert"]),
        product_bytes(shape["rows"], shape["experts"], shape["d_model"],
                      shape["d_expert"], shape["itemsize"]), peak)
    return 1e3 * seconds * products_a_step(shape["layers"],
                                           facts["remat"]), bound


def read(trace, run):
    spent = moe_ms.under(trace, (moe_ms.EXPERTS,))
    if not spent or "moe" not in run["facts"] or run["peak"] is None:
        return None
    least, bound = least_ms(run["facts"], run["peak"])
    print(f"moe_experts_roofline: bound by {bound}; least {least:.6f} ms "
          f"over {spent:.6f} ms a step", flush=True)
    return 100.0 * least / spent
