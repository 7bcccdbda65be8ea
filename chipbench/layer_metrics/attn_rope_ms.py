"""Time chip 0 spends a step under the scope ``attn_rope`` of
``horovod_tpu/models/transformer.py``: the rotary on ``q`` and ``k`` of
every attention layer that turns them, whatever law each turns by (the
plain one in a windowed layer, the YaRN-scaled one in a full layer of a
model with ``rotary_scaling``), the tables XLA makes for it and the
kernel's calls or the plain body's fusions; forward, recomputed and
backward. A part of ``attn_elementwise_ms``. What says that a second law
costs tables and no pass: the time a layer is the same under either law.
Left out where the program has no such scope."""
from chipbench import kernel_calls
from chipbench.layer_metrics import attn_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, (attn_ms.ROPE,))
