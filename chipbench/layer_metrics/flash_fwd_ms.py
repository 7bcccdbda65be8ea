"""Time chip 0 spends a step in the flash forward kernel: the Pallas
calls named ``hvt_flash_fwd`` (``ops/flash_attention.py``), whatever the
mixer that calls them (``Attention``, latent, sparse) and whatever the
pass, the first and the recomputed. Chosen by the kernel's name and not
as "every Pallas call" (``flash_ms``), so it reads the same in a program
whose experts, router or mixers run Pallas kernels of their own. Left
out where the program has no such call."""
from chipbench import kernel_calls

UNIT = "ms/step"
LAYER = "kernels"
MOVES = "tok_s_chip"

KERNEL = "hvt_flash_fwd"


def read(trace, run):
    return kernel_calls.kernel_ms(trace, run, KERNEL)
