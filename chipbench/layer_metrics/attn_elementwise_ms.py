"""Time chip 0 spends a step under the scopes ``attn_norm``, ``attn_rope``
and ``attn_gate`` of ``horovod_tpu/models/transformer.py``: the RMSNorm
over the projected width or a head, the rotary on ``q`` and ``k`` and the
sigmoid gate on the output, float32 elementwise passes over ``[b, s, h,
d]`` each; forward, recomputed and backward. The part of ``attn_ms`` that
is neither a product nor the kernels. Left out where the program has none
of the three."""
from chipbench import kernel_calls
from chipbench.layer_metrics import attn_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, (attn_ms.NORM, attn_ms.ROPE,
                                         attn_ms.GATE))
