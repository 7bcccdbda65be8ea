"""Mean time of one call of the flash forward kernel (``hvt_flash_fwd``)
where ``remat`` runs a block again: the calls whose name stack holds
``rematted_computation``. ``flash_fwd_first_call_ms``'s other half. Left
out where the program recomputes no such call."""
from chipbench import kernel_calls
from chipbench.layer_metrics import flash_fwd_ms

UNIT = "ms/call"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.call_ms(trace, run, flash_fwd_ms.KERNEL,
                                "recompute")
