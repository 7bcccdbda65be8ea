"""Mean time of one call of the flash forward kernel (``hvt_flash_fwd``)
in the first pass: the calls whose name stack names the forward pass
(``regions.naming_part``). Beside ``flash_fwd_again_call_ms``, the same
kernel on the same shapes where a block is recomputed: the two differ
only by what surrounds the call, the placement of its operands and
outputs (PERF.md section 6, PR 52). Left out where the program has no
such call."""
from chipbench import kernel_calls
from chipbench.layer_metrics import flash_fwd_ms

UNIT = "ms/call"
LAYER = "kernels"
MOVES = "tok_s_chip"


def read(trace, run):
    return kernel_calls.call_ms(trace, run, flash_fwd_ms.KERNEL,
                                "forward")
