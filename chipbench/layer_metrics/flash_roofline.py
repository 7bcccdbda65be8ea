"""Least time the chip could take for the flash-attention calls that ran
(forward, dQ, dK/dV; recomputed forwards included), at its published
peaks, over the time they took; in percent. The kernels carry no names of
their own in the trace (all three are ``%attn.N``), so a call's kind is
read from its signature: the forward returns (o, lse), dK/dV returns a
pair of gradients, dQ a single array."""
from chipbench import flops, xplane

UNIT = "%"
LAYER = "kernels"
MOVES = "tok_s_chip"


def kind(text: str) -> str:
    out = text.split(" = ", 1)[1]
    if not out.startswith("("):
        return "dq"
    first, second = out[1:].split(", ", 2)[:2]
    return "fwd" if second.split("[")[0] != first.split("[")[0] else "dkv"


def read(trace, run):
    shape = run["facts"].get("attention")
    if trace is None or run["peak"] is None or not shape:
        return None
    device = trace.devices[0]
    window = trace.window(device)
    if window is None:
        return None
    lo, hi, _ = window
    least = spent = 0.0
    bounds = set()
    for op in device.ops:
        if op.kind != "kernel" or not xplane.clip([(op.start, op.end)],
                                                  lo, hi):
            continue
        k = kind(op.text)
        seconds, bound = flops.roofline_seconds(
            flops.flash_call_flops(k, **shape),
            flops.flash_call_bytes(k, **shape), run["peak"])
        least += seconds
        spent += (op.end - op.start) / 1e9
        bounds.add(bound)
    if not spent:
        return None
    print(f"flash_roofline: bound by {'/'.join(sorted(bounds))}; least "
          f"{least:.6f} s over {spent:.6f} s", flush=True)
    return 100.0 * least / spent
