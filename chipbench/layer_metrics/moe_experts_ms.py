"""Time chip 0 spends a step under the scope ``moe_experts`` of
``horovod_tpu/models/moe.py``: the grouped products (forward, recomputed
and backward), the casts of the float32 weight stacks to the
multiplication's dtype and the gated activation between the products.
Left out where the program has no such scope."""
from chipbench.layer_metrics import moe_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return moe_ms.under(trace, (moe_ms.EXPERTS,))
