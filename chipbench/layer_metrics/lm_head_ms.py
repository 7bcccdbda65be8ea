"""Time chip 0 spends a step in the vocabulary projection
(``horovod_tpu.models.GPT``'s float32 einsum under the ``lm_head``
scope), forward and backward together, so it overlaps ``fwd_ms`` and
``bwd_ms`` by construction; the softmax and the loss that follow are not
in it. Left out where the program has no such scope."""
from chipbench import regions

UNIT = "ms/step"
LAYER = "models"
MOVES = "mfu"           # throughput in the unit every cell has


def read(trace, run):
    return regions.read(trace, ("forward", "recompute", "backward"),
                        scope="/lm_head/") or None
