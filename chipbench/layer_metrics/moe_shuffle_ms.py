"""Time chip 0 spends a step moving tokens to their experts and back:
every operation under the scopes ``moe_route`` (router, top-k, the count
and the two sorts), ``moe_dispatch`` (rows gathered into expert order)
and ``moe_combine`` (rows gathered back, weighted and summed) of
``horovod_tpu/models/moe.py``, forward, recomputed and backward. Memory-
and latency-bound work beside ``moe_experts_ms``' products. Left out
where the program has no such scope."""
from chipbench.layer_metrics import moe_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return moe_ms.under(trace, moe_ms.SHUFFLE)
