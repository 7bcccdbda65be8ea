"""Time chip 0 spends a step in the attention mixers of
``horovod_tpu/models/transformer.py`` (``Attention``): every operation
under one of its six scopes ``attn_proj``, ``attn_norm``, ``attn_rope``,
``attn_core``, ``attn_gate`` and ``attn_out_proj`` (a configuration makes
only those it has a piece for), forward, recomputed and backward together,
so it overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by
construction. ``while`` and ``conditional`` events are left out
(``chipbench/kernel_calls.py``). Left out where the program has no such
scope: a latent or a sparse attention, the parent's program, or a
compile-cache entry from before the scopes."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

NORM, ROPE, CORE, GATE = ("/attn_norm/", "/attn_rope/", "/attn_core/",
                          "/attn_gate/")
SCOPES = ("/attn_proj/", NORM, ROPE, CORE, GATE, "/attn_out_proj/")


def read(trace, run):
    return kernel_calls.scope_ms(trace, run, SCOPES)
