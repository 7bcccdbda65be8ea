"""Time chip 0 spends a step under the scope ``dsa_core`` of
``horovod_tpu/models/dsa.py``: scores, softmax and values over the chosen
keys (the flash kernels with a choice) and what XLA puts around them, the
layout changes into and out of the kernels' ``[b, h, s, d]``, the backward
pass's ``delta`` and the grouped keys' sums; forward, recomputed and
backward. Left out where the program has no such scope."""
from chipbench.layer_metrics import dsa_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return dsa_ms.under(trace, (dsa_ms.CORE,))
