"""Time chip 0 spends a step under the scope ``attn_blocks`` of
``horovod_tpu/models/transformer.py`` **outside the Pallas kernels**: what
XLA makes of a block-diffusion layer's products over positions around the
flash kernels' calls: a block's noised rows on their own noised keys
(``attn_blocks_own``: ``L / B`` products of ``B x B`` and a softmax of
``B`` scores), the merge of a noised row's two parts by their log-sum-exps
in float32 (``attn_blocks_merge``), the layout changes into and out of the
kernels' ``[b, h, s, d]``, the halves cut apart and put together, the
backward pass's ``delta`` and the sum of ``dk`` and ``dv`` over a group's
query heads; forward, recomputed and backward. ``attn_blocks_core_ms`` less
the kernels under it. Left out where the program has no such scope."""
from chipbench.layer_metrics.attn_blocks_core_ms import under_the_scope_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"


def read(trace, run):
    return under_the_scope_ms(trace, run, kernels=False)
