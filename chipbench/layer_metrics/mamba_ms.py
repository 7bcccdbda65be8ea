"""Time chip 0 spends a step in the Mamba-1 mixers
(``horovod_tpu/models/mamba.py``): every operation under one of its six
scopes ``mamba_in_proj``, ``mamba_conv``, ``mamba_step``, ``mamba_scan``,
``mamba_gate`` and ``mamba_out_proj``, forward, recomputed and backward
together, so it overlaps ``fwd_ms``, ``recompute_ms`` and ``bwd_ms`` by
construction. ``while`` events are left out, as ``kda_ms`` leaves them out:
a loop's event spans its body's, and the scan's plain body holds two loops
(the chunks, the passes inside one). Left out where the program has no such
scope."""
from chipbench.layer_metrics import kda_ms

UNIT = "ms/step"
LAYER = "models"
MOVES = "tok_s_chip"

SCAN = "/mamba_scan/"
SCOPES = ("/mamba_in_proj/", "/mamba_conv/", "/mamba_step/", SCAN,
          "/mamba_gate/", "/mamba_out_proj/")


def read(trace, run):
    return kda_ms.under(trace, SCOPES)
