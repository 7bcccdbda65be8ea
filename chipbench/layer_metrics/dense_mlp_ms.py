"""Time chip 0 spends a step in the dense MLP layers
(``horovod_tpu.models.transformer.MLP``, gated or not): every operation
under its scope ``dense_mlp``, forward, recomputed and backward together,
so it overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by
construction. Left out where the program has no such scope."""
from chipbench.layer_metrics.moe_ms import under

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return under(trace, ("/dense_mlp/",))
