"""Time chip 0 spends a step in the expert layers' grouped products: the
Pallas calls named ``gmm`` and ``tgmm`` (megablox's own names; the
products of a group's rows with its expert's matrices, and the weights'
gradients), every pass. The part of ``moe_experts_ms`` that is a product;
the rest of that scope is casts and activations. Left out where the
program has no such call."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.kernel_ms(trace, run, "gmm", "tgmm")
