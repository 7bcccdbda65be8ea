"""Time chip 0 spends a step under the scope ``attn_window`` that
``horovod_tpu/models/transformer.py`` opens inside ``attn_core`` in a
layer whose queries see a window of keys (pattern letter ``W``): the
products over positions of the windowed layers alone (the flash kernels,
or two einsums and a softmax) and what XLA puts around them, forward,
recomputed and backward. ``attn_core_ms`` less this is the layers' that
see every causal key. Left out where the program has no such scope."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

SCOPE = "/attn_window/"


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, (SCOPE,))
