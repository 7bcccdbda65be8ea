"""Time chip 0 spends a step under the scope ``sconv_gate_conv`` of
``horovod_tpu/models/sconv.py``: the gate ``B * u``, the causal depthwise
convolution's taps and the gate ``C *``, float32 inside, forward,
recomputed and backward. The part of ``sconv_ms`` that is no plain
projection: what one fused kernel would replace. Left out where the
program has no such scope."""
from chipbench.layer_metrics import sconv_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return sconv_ms.under(trace, (sconv_ms.GATE_CONV,))
