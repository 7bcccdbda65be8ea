"""Time chip 0 spends a step under the scope ``dsa_select`` of
``horovod_tpu/models/dsa.py``: the ``topk``-th largest index score of
every query's causal row and the choice's hand-over as a mask. Left out
where the program has no such scope."""
from chipbench.layer_metrics import dsa_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return dsa_ms.under(trace, (dsa_ms.SELECT,))
