"""Bytes chip 0's collective instructions put out a step, in MB (1e6
bytes), from the output shapes in the instructions' own text: for an
all-reduce the payload. Of an asynchronous pair the ``-done`` is counted,
whose output is the result alone."""
import math
import re

from chipbench import xplane

UNIT = "MB/step"
LAYER = "gradient path"
MOVES = "tok_s_chip"

_SHAPE = re.compile(r"\b(pred|[a-z]+?(\d+))\[([\d,]*)\]")


def output_bytes(text: str) -> int:
    """``%n = (bf16[8,4]{...}, f32[2]{...}) opcode(operands)`` -> 72."""
    shapes = text.split(" = ", 1)[1].split(f" {xplane.opcode(text)}(", 1)[0]
    return sum(int(bits or 8) // 8 * math.prod(map(int, filter(None,
               dims.split(",")))) for _, bits, dims in _SHAPE.findall(shapes))


def calls_and_bytes(trace):
    """(instructions, bytes) a step of chip 0's collectives that start
    inside the window; None without a trace or a window."""
    if trace is None:
        return None
    device = trace.devices[0]
    window = trace.window(device)
    if window is None:
        return None
    lo, hi, steps = window
    ran = [o for o in device.ops if o.kind == "collective"
           and lo <= o.start < hi and not o.opcode.endswith("-start")]
    return len(ran) / steps, sum(output_bytes(o.text) for o in ran) / steps


def read(trace, run):
    found = calls_and_bytes(trace)
    return None if found is None else found[1] / 1e6
