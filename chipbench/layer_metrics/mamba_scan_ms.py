"""Time chip 0 spends a step under the scope ``mamba_scan`` of
``horovod_tpu/models/mamba.py``: ``-exp(A_log)``, the decays' exponents,
the state's walk over the positions and the sum over the state, all
float32, and the skip term; forward, recomputed and backward. The part of
``mamba_ms`` that is neither a projection, a convolution nor a gate. Left
out where the program has no such scope."""
from chipbench.layer_metrics import kda_ms, mamba_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kda_ms.under(trace, (mamba_ms.SCAN,))
