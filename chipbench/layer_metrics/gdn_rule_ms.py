"""Time chip 0 spends a step under the scope ``gdn_rule`` of
``horovod_tpu/models/gdn.py``: the gates' sigmoid and softplus, the L2
norms of q and k, the decays and their cumulative sums in float32, a
chunk's triangular system and its inverse, the products inside the
chunks and the carry over them; forward, recomputed and backward. The
part of ``gdn_ms`` that is not a plain projection, a convolution or a
norm. Left out where the program has no such scope."""
from chipbench.layer_metrics import gdn_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return gdn_ms.under(trace, (gdn_ms.RULE,))
