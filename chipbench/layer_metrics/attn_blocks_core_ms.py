"""Time chip 0 spends a step under the scope ``attn_blocks`` that
``horovod_tpu/models/transformer.py`` opens inside ``attn_core`` in a
block-diffusion layer (``GPTConfig.diffusion_block``): the products over
positions of a clean and a noised copy of every sequence, which are the
flash kernels' two causal walks of a copy's positions (the clean rows on
the clean keys; the noised rows on the clean keys of the blocks before
theirs), a block's noised rows on its own noised keys in plain
``jax.numpy`` (``attn_blocks_own``), the merge of a noised row's two parts
by their log-sum-exps (``attn_blocks_merge``) and what XLA puts around them;
forward, recomputed and backward. ``attn_blocks_roofline`` reads the
kernels of it and ``attn_blocks_merge_ms`` the rest. Left out where the
program has no such scope."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

SCOPE = "/attn_blocks/"


def under_the_scope_ms(trace, run, kernels: bool):
    """ms a step of chip 0's events under ``attn_blocks`` that are
    (``kernels``) or are not Pallas calls; None where there is no trace, no
    program in it, no such scope or no such event."""
    found = kernel_calls.window(trace, run)
    if found is None:
        return None
    return sum(e.ns for e in found.events if SCOPE in e.part
               and e.kernel == kernels) / found.steps / 1e6 or None


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, (SCOPE,))
