"""Time chip 0 spends a step in the Gated DeltaNet mixers
(``horovod_tpu/models/gdn.py``): every operation under one of its five
scopes ``gdn_in_proj``, ``gdn_conv``, ``gdn_rule``, ``gdn_gate_norm`` and
``gdn_out_proj``, forward, recomputed and backward together, so it
overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by construction.
Left out where the program has no such scope."""
from chipbench.layer_metrics.moe_ms import under

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

RULE = "/gdn_rule/"
SCOPES = ("/gdn_in_proj/", "/gdn_conv/", RULE, "/gdn_gate_norm/",
          "/gdn_out_proj/")


def read(trace, run):
    return under(trace, SCOPES)
