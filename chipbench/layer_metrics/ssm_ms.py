"""Time chip 0 spends a step in the Mamba-2 mixers
(``horovod_tpu/models/ssm.py``): every operation under one of its five
scopes ``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate_norm`` and
``ssm_out_proj``, forward, recomputed and backward together, so it
overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by construction.
Left out where the program has no such scope."""
from chipbench.layer_metrics.moe_ms import under

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

SCAN = "/ssm_scan/"
SCOPES = ("/ssm_in_proj/", "/ssm_conv/", SCAN, "/ssm_gate_norm/",
          "/ssm_out_proj/")


def read(trace, run):
    return under(trace, SCOPES)
