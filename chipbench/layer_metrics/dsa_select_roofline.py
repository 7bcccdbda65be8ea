"""Least time the chip could take to read the float32 index scores of
every causal pair once and write a byte a pair of the choice, at its
published memory bandwidth, over the time of the **Pallas kernel calls
whose ``op_name`` holds ``/dsa_select/``** (the bisection kernel of
``ops/dsa.py``, once a layer and step: the choice is kept for the
backward pass); in percent. The bytes are counted from the cell's shapes
alone (``facts["dsa"]["select_bytes_per_step"]``). Left out where the
program has no such kernel."""
from chipbench.layer_metrics import dsa_ms

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    shape = run.get("facts", {}).get("dsa")
    spent = dsa_ms.under(trace, (dsa_ms.SELECT,), kernels_only=True)
    if not spent or not shape or run.get("peak") is None:
        return None
    least = 1e3 * shape["select_bytes_per_step"] / run["peak"][
        "hbm_bytes_per_s"]
    print(f"dsa_select_roofline: least {least:.6f} ms over {spent:.6f} ms "
          f"a step", flush=True)
    return 100.0 * least / spent
