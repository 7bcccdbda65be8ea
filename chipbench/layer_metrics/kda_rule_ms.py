"""Time chip 0 spends a step under the scope ``kda_rule`` of
``horovod_tpu/models/kda.py``: the gates' sigmoid and softplus, the L2
norms of q and k, the channel-wise decays and their cumulative sums in
float32, the decays folded into the products' operands, a chunk's
triangular system and its inverse, the products inside the chunks and the
carry over them; forward, recomputed and backward. The part of ``kda_ms``
that is not a plain projection, a convolution or a norm. Left out where
the program has no such scope."""
from chipbench.layer_metrics import kda_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kda_ms.under(trace, (kda_ms.RULE,))
