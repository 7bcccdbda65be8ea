"""Time chip 0 spends a step under the scope ``attn_core`` of
``horovod_tpu/models/transformer.py``: the products over positions (the
flash kernels, or the ring schedule, or two einsums and a softmax) and
what XLA puts around them, the layout changes into and out of the
kernels' ``[b, h, s, d]``, the backward pass's ``delta`` and the sum of
``dk`` and ``dv`` over a group's query heads; forward, recomputed and
backward. At least ``flash_fwd_ms + flash_bwd_ms`` where the kernels
serve; the difference is XLA's. Left out where the program has no such
scope."""
from chipbench import kernel_calls
from chipbench.layer_metrics import attn_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, (attn_ms.CORE,))
