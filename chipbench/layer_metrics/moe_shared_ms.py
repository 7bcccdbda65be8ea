"""Time chip 0 spends a step in the part of an expert layer that every
token passes (``horovod_tpu/models/moe.py``): the shared expert under the
scope ``moe_shared`` and the two latent projections around the routed
experts under ``moe_latent``, forward, recomputed and backward. Plain
products beside ``moe_ms``' routing, shuffle and grouped products; not
part of ``moe_ms``. Left out where the program has no such scope."""
from chipbench.layer_metrics.moe_ms import under

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

SCOPES = ("/moe_shared/", "/moe_latent/")


def read(trace, run):
    return under(trace, SCOPES)
