"""Time chip 0 spends a step in the gated memory units
(``horovod_tpu/models/mamba.py``, ``GatedMemoryUnit``): every operation
under one of its three scopes ``gmu_in_proj``, ``gmu_gate`` and
``gmu_out_proj``, forward, recomputed and backward together. Left out
where the program has no such scope."""
from chipbench.layer_metrics import kda_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

SCOPES = ("/gmu_in_proj/", "/gmu_gate/", "/gmu_out_proj/")


def read(trace, run):
    return kda_ms.under(trace, SCOPES)
