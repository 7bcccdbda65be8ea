"""Time chip 0 spends a step under the scope ``attn_diff`` that
``horovod_tpu/models/transformer.py`` opens inside ``attn_core`` in a
differential attention layer: the difference of the two softmax maps'
results, the norm a pair of heads and the scale, float32, forward,
recomputed and backward; what the layers' products over positions cost
beside the kernels' calls. Left out where the program has no such
scope."""
from chipbench.layer_metrics import kda_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kda_ms.under(trace, ("/attn_diff/",))
