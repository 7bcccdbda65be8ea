"""Time chip 0 spends a step in the norms after the mixers
(``horovod_tpu.models.transformer.MixerBlock`` under ``post_norm``: a
layer is ``x + post_norm(mixer(norm(x)))``): every operation under the
scope ``post_norm``, forward, recomputed and backward together, so it
overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by construction. Left
out where the program has no such scope."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, ("/post_norm/",))
