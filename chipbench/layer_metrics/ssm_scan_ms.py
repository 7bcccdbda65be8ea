"""Time chip 0 spends a step under the scope ``ssm_scan`` of
``horovod_tpu/models/ssm.py``: the step size's softplus, the decays and
their cumulative sums in float32, the masked products inside the chunks,
the states the chunks hand on, the carry over the chunks and the skip
term; forward, recomputed and backward. The part of ``ssm_ms`` that is
not a plain projection. Left out where the program has no such scope."""
from chipbench.layer_metrics import ssm_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return ssm_ms.under(trace, (ssm_ms.SCAN,))
