"""Time chip 0 spends a step in the flash backward kernel: the Pallas
calls named ``hvt_flash_bwd`` (``ops/flash_attention.py``), whatever the
mixer that calls them. ``flash_fwd_ms``'s other half. Left out where the
program has no such call."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.kernel_ms(trace, run, "hvt_flash_bwd")
