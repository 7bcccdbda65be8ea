"""The Gated DeltaNet mixer's per-head norms alone, on the chip: the four
Pallas kernels of ``ops/head_norm.py`` beside the plain ``jax.numpy`` bodies
(``gated_norm_plain``, ``l2_norm_plain``), at the shapes
``qwen3next-s8192`` runs them: ``RMSNorm(o) w silu(z)`` over 32 heads of
128, ``[2, 8192, 4096]``, and the L2 norm over 16 heads of 128, ``[2, 8192,
2048]``, bf16, flat ``[b, s, H d]`` in and out, which is what the mixer's
neighbours (the convolution's and the rule's kernels, the out-projection)
hold.

For each path a forward call and a forward-and-backward call (``jax.vjp``
for all the operands): device milliseconds a call (the time chip 0's
operations cover in a profiler trace of ``--calls`` calls, over the calls),
the kernels' own events by name, wall-clock milliseconds a call, and beside
them the least time the bytes allow at 819 GB/s (``chipbench/peaks.json``'s
HBM rate for a v5e): the gated norm's forward reads ``o`` and ``z`` and
writes ``y``, its backward reads ``o``, ``z`` and ``dy`` and writes ``do``
and ``dz``; the L2 norm's forward reads and writes once, its backward reads
``x`` and ``dy`` and writes ``dx``. ``--blocks`` times other blocks than the
kernels' own: ``ROWSxLANESxSUB`` (positions and channels a grid step, rows a
pass of the loop inside).

A microbenchmark: a path alone is not its cost inside the step (PERF.md
section 6, PRs 27 and 36); the cell that decides is ``qwen3next-s8192`` of
``BENCHMARK.json``.

    chiprun -- python benchmarks/head_norm_kernels.py --blocks derived,1024x512x32
"""

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.dirname(os.path.abspath(__file__))]

import causal_conv as bench  # noqa: E402  (beside this file: its timer)

KERNELS = ("hvt_gated_norm_fwd", "hvt_gated_norm_bwd", "hvt_l2_norm_fwd",
           "hvt_l2_norm_bwd")
EPS = 1e-6
# norm: (batch, seq, heads, dim), arrays of that size a forward and a
# forward-and-backward move
SHAPES = {"gated": ((2, 8192, 32, 128), 3, 8), "l2": ((2, 8192, 16, 128), 2, 5)}


def _paths(norm, dim, blocks):
    from horovod_tpu.ops import head_norm as kernels

    def named(block):
        return {} if block == "derived" else dict(zip(
            ("rows", "lanes", "sub"), (int(n) for n in block.split("x"))))

    if norm == "gated":
        plain = lambda o, z, w: kernels.gated_norm_plain(o, z, w, eps=EPS)
        kernel = lambda block: lambda o, z, w: kernels.gated_norm_kernels(
            o, z, w, eps=EPS, **named(block))
    else:
        plain = lambda x: kernels.l2_norm_plain(
            x, dim, eps=EPS, scale=dim ** -0.5)
        kernel = lambda block: lambda x: kernels.l2_norm_kernels(
            x, dim, eps=EPS, scale=dim ** -0.5, **named(block))
    return [("plain", plain)] + [(f"kernels_{b}", kernel(b)) for b in blocks]


def measure(norm, blocks, calls):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chip_smoke import rel_l2

    (b, s, heads, dim), forward, both_ways = SHAPES[norm]
    rng = np.random.RandomState(0)
    wide = lambda: jnp.asarray(rng.normal(size=(b, s, heads * dim)),
                               jnp.bfloat16)
    args = (wide(), wide(), jnp.asarray(rng.uniform(0.5, 1.5, (dim,)),
                                        jnp.float32)
            ) if norm == "gated" else (wide(),)
    g = wide()
    array = b * s * heads * dim * 2
    out = {"shape": [b, s, heads * dim], "heads": heads, "dim": dim,
           "least_ms_forward": 1e3 * forward * array / bench.HBM_BYTES_PER_S,
           "least_ms_forward_and_backward":
               1e3 * both_ways * array / bench.HBM_BYTES_PER_S}
    first = None
    for name, fn in _paths(norm, dim, blocks):
        both = jax.jit(lambda *a, fn=fn: (lambda o, vjp: (o, *vjp(g)))(
            *jax.vjp(fn, *a)))
        try:
            got = jax.device_get(both(*args))
        except Exception as e:      # a block the compiler refuses: say so
            out[name] = {"refused": str(e).split("\n")[0][-300:]}
            continue
        first = got if first is None else first
        out[name] = {"rel_l2_vs_plain": rel_l2(got, first),
                     "forward": bench._time(jax.jit(fn), args, calls, KERNELS),
                     "forward_and_backward": bench._time(both, args, calls,
                                                         KERNELS)}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--norms", default=",".join(SHAPES),
                        help="of " + ", ".join(SHAPES))
    parser.add_argument("--blocks", default="derived",
                        help="derived or ROWSxLANESxSUB, comma-separated")
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/head_norm_kernels.py times the chip: "
                         "no TPU here")
    out = {"device": jax.devices()[0].device_kind, "norms": {}}
    for norm in args.norms.split(","):
        out["norms"][norm] = measure(norm, args.blocks.split(","),
                                     args.calls)
        print(json.dumps({norm: out["norms"][norm]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/head_norm_kernels.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
