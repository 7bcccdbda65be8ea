"""The causal depthwise convolution's two Pallas kernels alone, on the chip
(``ops/causal_conv.py``), beside the plain ``jax.numpy`` body
(``causal_conv_plain`` there), at the two shapes the benchmark's cells run:
``qwen3next-s8192``'s ``[2, 8192, 8192]`` without a bias and
``nemotron3s-s8192``'s ``[2, 8192, 1280]`` with one, bf16, four taps.

For each path a forward call and a forward-and-backward call (``jax.vjp``
for all the operands): device milliseconds a call (the time chip 0's
operations cover in a profiler trace of ``--calls`` calls, over the calls),
the kernels' own events by name, wall-clock milliseconds a call, and beside
them the least time the bytes allow at 819 GB/s (``chipbench/peaks.json``'s
HBM rate for a v5e): a forward reads ``x`` and writes the output once, a
backward reads ``x`` and the gradient and writes ``dx``. ``--blocks`` times
other blocks than the kernels' own: ``ROWSxLANESxSUB`` (positions and
channels a grid step, rows a pass of the loop inside).

A microbenchmark: a path alone is not its cost inside the step (PERF.md
section 6, PR 27); the cells that decide are ``qwen3next-s8192`` and
``nemotron3s-s8192`` of ``BENCHMARK.json``.

    chiprun -- python benchmarks/causal_conv.py --blocks derived,512x1024x16
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9
KERNELS = ("hvt_causal_conv_fwd", "hvt_causal_conv_bwd")
# (batch, seq, channels, bias): the cells' own
SHAPES = {"qwen3next-s8192": (2, 8192, 8192, False),
          "nemotron3s-s8192": (2, 8192, 1280, True)}


def _inputs(shape, taps):
    import jax.numpy as jnp
    import numpy as np

    b, s, c, with_bias = shape
    rng = np.random.RandomState(0)
    x, g = (jnp.asarray(rng.normal(size=(b, s, c)), jnp.bfloat16)
            for _ in range(2))
    weight = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, c)), jnp.float32)
    bias = (jnp.asarray(rng.normal(size=(c,)), jnp.float32) if with_bias
            else None)
    return (x, weight, bias), g


def _device_ms(trace_dir, calls, names=KERNELS):
    """``(ms a call chip 0 was busy, {kernel: ms a call})`` of a trace,
    for the kernels of ``names``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    spans, kernels = [], dict.fromkeys(names, 0.0)
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                spans.append((e.start_ns, e.start_ns + e.duration_ns))
                for name in names:
                    if name in e.name.split(" = ")[0]:
                        kernels[name] += e.duration_ns / 1e6 / calls
    busy, end = 0.0, 0
    for a, b in sorted(spans):
        busy += max(b, end) - max(a, end)
        end = max(b, end)
    return busy / 1e6 / calls, {k: v for k, v in kernels.items() if v}


def _time(fn, args, calls, names=KERNELS):
    import jax

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        wall = 1e3 * (time.perf_counter() - t0) / calls
        jax.profiler.stop_trace()
        device, kernels = _device_ms(trace_dir, calls, names)
    return {"device_ms": device, "wall_ms": wall, **kernels}


def measure(shape, taps, blocks, calls):
    import jax
    from chip_smoke import rel_l2

    from horovod_tpu.ops import causal_conv as conv_op

    args, g = _inputs(shape, taps)
    b, s, c, _ = shape
    array = b * s * c * args[0].dtype.itemsize
    out = {"shape": list(shape), "taps": taps,
           "least_ms_forward": 1e3 * 2 * array / HBM_BYTES_PER_S,
           "least_ms_forward_and_backward": 1e3 * 5 * array / HBM_BYTES_PER_S}
    paths = [("plain", conv_op.causal_conv_plain)]
    for block in blocks:
        named = ({} if block == "derived" else dict(zip(
            ("rows", "lanes", "sub"), (int(n) for n in block.split("x")))))
        paths.append((f"kernels_{block}", lambda *a, named=named:
                      conv_op.causal_conv_kernels(*a, **named)))
    first = None
    for name, conv in paths:
        both = jax.jit(lambda *a, conv=conv: (lambda o, vjp: (o, *vjp(g)))(
            *jax.vjp(conv, *a)))
        try:
            got = jax.device_get(both(*args))
        except Exception as e:      # a block the compiler refuses: say so
            out[name] = {"refused": str(e).split("\n")[0][-300:]}
            continue
        first = got if first is None else first
        out[name] = {"rel_l2_vs_plain": rel_l2(got, first),
                     "forward": _time(jax.jit(conv), args, calls),
                     "forward_and_backward": _time(both, args, calls)}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES),
                        help="of " + ", ".join(SHAPES))
    parser.add_argument("--taps", type=int, default=4)
    parser.add_argument("--blocks", default="derived",
                        help="derived or ROWSxLANESxSUB, comma-separated")
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/causal_conv.py times the chip: no TPU "
                         "here")
    out = {"device": jax.devices()[0].device_kind, "cells": {}}
    for cell in args.shapes.split(","):
        out["cells"][cell] = measure(SHAPES[cell], args.taps,
                                     args.blocks.split(","), args.calls)
        print(json.dumps({cell: out["cells"][cell]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/causal_conv.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
