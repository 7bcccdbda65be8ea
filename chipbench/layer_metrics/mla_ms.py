"""Time chip 0 spends a step in the latent-attention mixers
(``horovod_tpu/models/mla.py``): every operation under one of its six
scopes ``mla_q_proj``, ``mla_kv_down``, ``mla_kv_up``, ``mla_rope``,
``mla_core`` and ``mla_out_proj``, forward, recomputed and backward
together, so it overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by
construction. Left out where the program has no such scope."""
from chipbench.layer_metrics.moe_ms import under

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

CORE = "/mla_core/"
SCOPES = ("/mla_q_proj/", "/mla_kv_down/", "/mla_kv_up/", "/mla_rope/", CORE,
          "/mla_out_proj/")


def read(trace, run):
    return under(trace, SCOPES)
