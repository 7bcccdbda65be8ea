"""The rotary of ``q`` and ``k`` alone, on the chip: the Pallas kernel of
``ops/rotary.py`` beside the plain ``jax.numpy`` body (``rotary_plain``)
and beside the kernel's own arithmetic left to XLA (``dense``: rotations
and a select on ``[b, s, h d]`` in ``jax.numpy``), at the five shapes the
benchmark's cells run, bf16: ``8,1024,20+20,64`` (``gpt2l-s1024``,
``gpt2l-dp4``), ``2,4096,20+20,64`` (``gpt2l-s4096``), ``1,16384,32+4,128``
(``trinitymini-s16384``, ``keyevl2-s16384``), ``2,8192,32,64``
(``kanana2-s8192``'s ``q_r``) and ``2,8192,16+2,256/64`` (``qwen3next-s8192``,
64 of 256 channels turned).

Every path is handed ``[b, h, s, d]``, which is how XLA has a projection's
product write what the flash kernels read, hands that layout on and takes
the gradient in it: the kernel as it lies, the plain body and ``dense``
under the transpositions the mixers and ``flash_attention`` make. For each
path a forward call and a forward-and-backward call: device milliseconds a
call (the time chip 0's operations cover in a profiler trace of ``--calls``
calls, over the calls: the tables' ``cos`` and ``sin`` are in it), the
kernel's own events by name, wall-clock milliseconds a call, and beside
them the least time the bytes allow at 819 GB/s (``chipbench/peaks.json``'s
HBM rate for a v5e) with the share of that rate a path reaches: a pass reads
and writes every array once, heads narrower than 128 lanes padded to 128.
``--blocks`` times other blocks than the kernel's own: ``ROWSxSUB``
(positions a grid step, which divide the sequence's, and rows a pass of
the loop inside).

A microbenchmark: a path alone is not its cost inside the step (PERF.md
section 6, PRs 27, 36 and 60: alone the plain body has no product before it
and no kernel behind it, XLA lays it out as it likes and it reads 0.13 ms a
pass where the step's three fusions take 0.46 to 0.77); the kernel's own
events are what carries over, and the cells that decide are
``BENCHMARK.json``'s.

    chiprun -- python benchmarks/rotary_kernels.py --blocks derived,128x32,256x16
"""

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.dirname(os.path.abspath(__file__))]

import causal_conv as bench  # noqa: E402  (beside this file: its timer)

KERNELS = ("hvt_rotary_fwd", "hvt_rotary_bwd")
BASE = 10000.0
SHAPES = ("8,1024,20+20,64", "2,4096,20+20,64", "1,16384,32+4,128",
          "2,8192,32,64", "2,8192,16+2,256/64")


def _shape(text):
    """``b,s,h[+h_k],d[/width]`` -> ``(b, s, heads, d, width)``."""
    text, _, width = text.partition("/")
    b, s, heads, d = text.split(",")
    return (int(b), int(s), tuple(int(h) for h in heads.split("+")), int(d),
            int(width) if width else None)


def _dense(x, positions, width):
    """The kernel's arithmetic in ``jax.numpy`` on ``[b, s, h d]``."""
    import jax.numpy as jnp
    from horovod_tpu.ops import rotary

    b, s, h, d = x.shape
    half = (width or d) // 2
    cos, sin = rotary._tables(positions, BASE, d, width or d, x.dtype)
    table = lambda t: jnp.tile(t[..., :d], h)
    flat = x.reshape(b, s, h * d)
    first = (jnp.arange(h * d) % d) < half
    partner = jnp.where(first, jnp.roll(flat, -half, -1),
                        jnp.roll(flat, half, -1))
    return (flat * table(cos) + partner * table(sin)).reshape(x.shape)


def _paths(width, blocks):
    from horovod_tpu.ops import rotary

    def named(block):
        return {} if block == "derived" else dict(zip(
            ("rows", "sub"), (int(n) for n in block.split("x"))))

    each = lambda fn: lambda xs, p: tuple(fn(x, p) for x in xs)
    return [("plain", each(lambda x, p: rotary.rotary_plain(
                x, p, BASE, width))),
            ("dense", each(lambda x, p: _dense(x, p, width)))] + [
        (f"kernel_{b}", lambda xs, p, b=b: rotary.rotary_kernels(
            xs, p, BASE, width, **named(b)))
        for b in blocks]


def measure(text, blocks, calls):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chip_smoke import rel_l2

    b, s, heads, d, width = _shape(text)
    rng = np.random.RandomState(0)
    major = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    heads_major = lambda: tuple(
        jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.bfloat16)
        for h in heads)
    xs, gs = heads_major(), heads_major()
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # read and written heads major, a head's lanes padded to 128
    moved = sum(x.size * 2 * 2 * max(128 // d, 1) for x in xs)
    out = {"shape": text, "least_ms_a_pass": 1e3 * moved / bench.HBM_BYTES_PER_S}
    first = None
    for name, fn in _paths(width, blocks):
        forward = jax.jit(lambda xs, fn=fn: tuple(major(o) for o in fn(
            tuple(major(x) for x in xs), positions)))
        both = jax.jit(lambda xs, gs, forward=forward: (
            lambda o, vjp: (o, *vjp(gs)))(*jax.vjp(forward, xs)))
        try:
            got = jax.device_get(both(xs, gs))
        except Exception as e:      # a block the compiler refuses: say so
            out[name] = {"refused": str(e).split("\n")[0][-300:]}
            continue
        first = got if first is None else first
        here = out[name] = {
            "rel_l2_vs_plain": rel_l2(got, first),
            "forward": bench._time(forward, (xs,), calls, KERNELS),
            "forward_and_backward": bench._time(both, (xs, gs), calls,
                                                KERNELS)}
        for passes, key in ((1, "forward"), (2, "forward_and_backward")):
            here[key]["share_of_hbm_rate"] = (
                passes * out["least_ms_a_pass"] / here[key]["device_ms"])
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default=" ".join(SHAPES),
                        help="b,s,h[+h_k],d[/width], space-separated")
    parser.add_argument("--blocks", default="derived",
                        help="derived or ROWSxSUB, comma-separated")
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/rotary_kernels.py times the chip: "
                         "no TPU here")
    out = {"device": jax.devices()[0].device_kind, "shapes": []}
    for text in args.shapes.split():
        out["shapes"].append(measure(text, args.blocks.split(","),
                                     args.calls))
        print(json.dumps(out["shapes"][-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/rotary_kernels.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
