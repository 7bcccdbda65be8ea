#!/usr/bin/env python
"""moe_grouped_product.py — the expert layer's grouped products alone, on
the chip: ``jax.lax.ragged_dot`` against JAX's Pallas grouped product
(``jax.experimental.pallas.ops.tpu.megablox.gmm``).

    chiprun -- python benchmarks/moe_grouped_product.py \
        --rows 65536 --d 2048 --f 1024 --experts 64 \
        --candidates ragged_dot,gmm:512x1024x1024,gmm:512x2048x1024

For every candidate it jits the SwiGLU expert chain of
``horovod_tpu/models/moe.py`` (``down(silu(gate(x)) * up(x))``, f32 weight
stacks cast to bf16, rows already in expert order) forward and with its
``vjp``, runs both ``--iters`` times inside one profiler trace and prints
one JSON line: wall-clock ms of the forward and of forward + backward,
the device ms a call of chip 0's slowest operations (by instruction name),
and how far the results are from the first candidate's (relative L2).
``--skew`` gives one expert half of the rows and leaves one empty.

A microbenchmark, not the yardstick: the cell that decides is
``olmoe-s4096`` of ``BENCHMARK.json``. It refuses to run without a TPU.
"""

import argparse
import collections
import glob
import json
import os
import statistics
import sys
import tempfile
import time


def products(name):
    """``product(lhs [m, k], rhs [g, k, n], group_sizes) -> [m, n]``."""
    import jax
    import jax.numpy as jnp

    if name == "ragged_dot":
        return jax.lax.ragged_dot
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiling = tuple(int(x) for x in name.split(":")[1].split("x"))

    def clipped(m, k, n):       # a tile no larger than the product's side
        return (min(tiling[0], m), min(tiling[1], k), min(tiling[2], n))

    return lambda lhs, rhs, sizes: gmm(
        lhs, rhs, sizes, preferred_element_type=jnp.bfloat16,
        tiling=clipped)


def experts(product):
    import jax
    import jax.numpy as jnp

    def forward(x, gate, up, down, sizes):
        gate, up, down = (w.astype(jnp.bfloat16) for w in (gate, up, down))
        h = jax.nn.silu(product(x, gate, sizes)) * product(x, up, sizes)
        return product(h, down, sizes)

    return forward


def op_ms(trace_dir, calls):
    """{instruction name: ms a call} of chip 0's ten slowest."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    total = collections.Counter()
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    total[e.name.split(" = ")[0]] += e.duration_ns / 1e6
    return {k: v / calls for k, v in total.most_common(10)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--f", type=int, default=1024)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--candidates",
                    default="ragged_dot,gmm:512x1024x1024")
    ap.add_argument("--skew", action="store_true")
    ap.add_argument("--iters", type=int, default=5)
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chip_smoke import rel_l2

    if jax.default_backend() != "tpu":
        raise SystemExit("moe_grouped_product.py times products on a TPU; "
                         f"found {jax.default_backend()}")
    rng = np.random.RandomState(0)
    share = np.full(a.experts, 1.0 / a.experts)
    if a.skew:
        share = np.r_[0.5, 0.0, np.full(a.experts - 2,
                                        0.5 / (a.experts - 2))]
    sizes = jnp.asarray(rng.multinomial(a.rows, share), jnp.int32)
    keys = jax.random.split(jax.random.key(0), 5)
    x, dy = (jax.random.normal(k, (a.rows, a.d), jnp.bfloat16)
             for k in keys[:2])
    gate, up = (0.02 * jax.random.normal(k, (a.experts, a.d, a.f))
                for k in keys[2:4])
    down = 0.02 * jax.random.normal(keys[4], (a.experts, a.f, a.d))
    first = None
    for name in a.candidates.split(","):
        line = {"candidate": name, "rows": a.rows, "d": a.d, "f": a.f,
                "experts": a.experts, "skew": a.skew,
                "largest_group": int(sizes.max()),
                "device": jax.devices()[0].device_kind}
        forward = jax.jit(experts(products(name)))

        @jax.jit
        def both(x, gate, up, down, sizes, dy, forward=forward):
            y, vjp = jax.vjp(lambda x, g, u, d: forward(x, g, u, d, sizes),
                             x, gate, up, down)
            return (y, *vjp(dy))

        try:
            got = jax.device_get(both(x, gate, up, down, sizes, dy))
            jax.block_until_ready(forward(x, gate, up, down, sizes))
        except Exception as e:      # a tile the compiler refuses: say so
            line["refused"] = str(e).split("\n")[0][-300:]
            print(json.dumps(line), flush=True)
            continue
        first = got if first is None else first
        line["rel_l2_vs_first"] = rel_l2(got, first)
        for label, fn, args in (
                ("forward", forward, (x, gate, up, down, sizes)),
                ("forward_backward", both, (x, gate, up, down, sizes, dy))):
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                t0 = time.perf_counter()
                for _ in range(a.iters):
                    out = fn(*args)
                jax.block_until_ready(out)
                line[f"{label}_wall_ms"] = (
                    time.perf_counter() - t0) * 1e3 / a.iters
                jax.profiler.stop_trace()
                line[f"{label}_op_ms"] = op_ms(trace_dir, a.iters)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
