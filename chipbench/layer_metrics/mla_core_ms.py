"""Time chip 0 spends a step under the scope ``mla_core`` of
``horovod_tpu/models/mla.py``: the products over positions (the flash
kernels at a query-key width apart from the value width) and what XLA puts
around them, the key's assembly (``k_n`` beside the shared ``k_r``
broadcast over the heads), the layout changes into and out of the
kernels' ``[b, h, s, d]`` and the backward pass's ``delta``; forward,
recomputed and backward. The part of ``mla_ms`` that is not a projection,
the latent's norm or the rotary. Left out where the program has no such
scope."""
from chipbench.layer_metrics import mla_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return mla_ms.under(trace, (mla_ms.CORE,))
