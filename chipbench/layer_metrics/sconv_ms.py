"""Time chip 0 spends a step in the gated short-convolution mixers
(``horovod_tpu/models/sconv.py``): every operation under one of its three
scopes ``sconv_in_proj``, ``sconv_gate_conv`` and ``sconv_out_proj``,
forward, recomputed and backward together, so it overlaps ``fwd_ms``,
``recompute_ms`` and ``bwd_ms`` by construction. Left out where the
program has no such scope."""
from chipbench.layer_metrics.moe_ms import under

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

GATE_CONV = "/sconv_gate_conv/"
SCOPES = ("/sconv_in_proj/", GATE_CONV, "/sconv_out_proj/")


def read(trace, run):
    return under(trace, SCOPES)
