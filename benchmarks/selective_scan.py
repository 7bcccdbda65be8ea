"""Mamba-1's selective scan alone, on the chip (``ops/selective_scan.py``):
the plain ``jax.numpy`` body and the two Pallas kernels in one process, at
the shape ``phi4flash-s16384`` runs (one sequence of 16,384 positions,
5,120 channels, a state of 16; bf16 operands and a float32 step size).

For each body a forward call and a forward-and-backward call (``jax.vjp``
for all five operands): device milliseconds a call (the time chip 0's
operations cover in a profiler trace of ``--calls`` calls, over the calls),
the kernels' own events by name, wall-clock milliseconds a call, and beside
them the least time the recurrence's bytes allow at 819 GB/s
(``chipbench/peaks.json``'s HBM rate for a v5e; the bytes as
``chipbench/families/phi4flash.py`` counts them a layer). ``--variants``
times other blocks than the kernels' own, ``FWDxBWD``: the channels a grid
step takes (and walks side by side in registers) going forward and going
back. Each body is also held to the recurrence a position at a
time (``selective_scan_by_position``, float32) on the first ``--check``
positions: the output's and the five gradients' relative distance.

``--count`` needs no chip and times nothing on one: each kernel call's
equations (loop and kernel bodies included, on the branch a TPU compiles),
and the host's seconds to trace it and to lower it for a TPU, first and
again with ``jit``'s caches cleared; the numbers the start's budget is
checked by before a timed pair (an equation of a kernel body costs the
cell 0.9 ms of ``trace_s`` and 0.2 ms of ``lower_s`` on every start:
PERF.md section 6, PRs 67 and 68).

A microbenchmark: a body alone is not its cost inside the step (PERF.md
section 6, PR 27); the cell that decides is ``phi4flash-s16384`` of
``BENCHMARK.json``.

    chiprun -- python benchmarks/selective_scan.py --variants derived,1024x512
    python benchmarks/selective_scan.py --count
"""

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.causal_conv import HBM_BYTES_PER_S, _time  # noqa: E402

KERNELS = ("hvt_mamba_scan_fwd", "hvt_mamba_scan_bwd")
# (batch, seq, channels, state): the cell's own
SHAPE = (1, 16384, 5120, 16)


def _inputs(shape, dtype, seed=0):
    """Operands as the mixer hands them: ``u`` after the convolution's
    ``silu``, steps of Mamba's own initial range (1e-3 to 1e-1), ``A[c, n]
    = -(n + 1)``, ``b`` and ``c`` of unit scale; and an output's gradient."""
    import jax
    import jax.numpy as jnp

    batch, seq, channels, n = shape
    ks = jax.random.split(jax.random.key(seed), 5)
    u = jax.nn.silu(jax.random.normal(ks[0], (batch, seq, channels)))
    delta = jnp.exp(jax.random.uniform(
        ks[1], (batch, seq, channels), jnp.float32, jnp.log(1e-3),
        jnp.log(1e-1)))
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (channels, n))
    b, c = (jax.random.normal(k, (batch, seq, n)) for k in ks[2:4])
    g = jax.random.normal(ks[4], (batch, seq, channels))
    return ((u.astype(dtype), delta, a, b.astype(dtype), c.astype(dtype)),
            g.astype(dtype))


def _both(scan, g):
    """``scan``'s output and its five gradients for the output's gradient
    ``g``."""
    import jax

    def both(*operands):
        o, vjp = jax.vjp(scan, *operands)
        return (o, *vjp(g))

    return both


def _distances(scan, operands, g, check):
    """``scan`` on the first ``check`` positions against the recurrence a
    position at a time, float32: relative distance of the output and of
    each operand's gradient."""
    import jax
    import jax.numpy as jnp
    from chip_smoke import rel_l2

    from horovod_tpu.ops import selective_scan as scan_op

    head = lambda t: t[:, :check] if t.ndim == 3 else t
    operands, g = tuple(map(head, operands)), head(g)
    got = jax.jit(_both(scan, g))(*operands)
    want = jax.jit(_both(scan_op.selective_scan_by_position,
                         g.astype(jnp.float32)))(*operands)
    return {name: rel_l2(x, y) for name, x, y in zip(
        ("y", "du", "ddelta", "da", "db", "dc"), got, want)}


def measure(shape, dtype, variants, calls, check):
    import jax

    from horovod_tpu.ops import selective_scan as scan_op

    operands, g = _inputs(shape, dtype)
    batch, seq, channels, n = shape
    block = batch * seq * channels
    # forward: u, delta, b, c read and y written; backward: those, the
    # output's gradient and the kept states read, du, ddelta, db, dc written
    itemsize = operands[0].dtype.itemsize
    forward = block * (2 * itemsize + 4) + 2 * batch * seq * n * itemsize
    kept = batch * (seq // scan_op.CHUNK) * n * channels * 4
    out = {"shape": list(shape), "dtype": str(operands[0].dtype),
           "least_ms_forward": 1e3 * forward / HBM_BYTES_PER_S,
           "least_ms_forward_and_backward": 1e3 * (
               3 * forward + block * (itemsize + 4) + 2 * kept
               ) / HBM_BYTES_PER_S}
    bodies = [("plain", scan_op.selective_scan_plain)]
    for variant in variants:
        named = ({} if variant == "derived" else dict(zip(
            ("fwd", "bwd"), (int(w) for w in variant.split("x")))))
        bodies.append((f"kernels_{variant}", functools.partial(
            scan_op.selective_scan_kernels, **named)))
    for name, scan in bodies:
        try:
            distances = _distances(scan, operands, g, check)
            times = {
                "forward": _time(jax.jit(scan), operands, calls, KERNELS),
                "forward_and_backward": _time(
                    jax.jit(_both(scan, g)), operands, calls, KERNELS)}
        except Exception as e:      # a block the compiler refuses: say so
            out[name] = {"refused": str(e).split("\n")[0][-300:]}
            continue
        out[name] = {"rel_l2_vs_by_position": distances, **times}
        print(json.dumps({name: out[name]}), flush=True)
    return out


def equations(jaxpr) -> int:
    """Equations of ``jaxpr`` and of every jaxpr inside it (a kernel's body,
    a loop's, a branch's), each counted once: what a trace walks, and what
    ``tests/test_chip_compile.py`` holds the two kernels to."""
    from chipbench.flops import _sub_jaxprs

    return sum(1 + sum(equations(inner) for inner in _sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def count(shape, dtype):
    """Each kernel call at ``shape``: equations, and seconds to trace and
    to lower for a TPU (``interpret=False``; a CPU can do both, though not
    compile), twice: what a process pays once and what it pays again."""
    import time

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import selective_scan as scan_op

    batch, seq, channels, n = shape
    like = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype)
    operands = (like(batch, seq, channels, dtype=dtype),
                like(batch, seq, channels), like(channels, n),
                like(batch, seq, n, dtype=dtype),
                like(batch, seq, n, dtype=dtype))
    plan = scan_op._plan(channels, scan_op.CHUNK, None, None)._replace(
        interpret=False)
    y, entered = jax.eval_shape(functools.partial(
        scan_op._fwd_call, plan=plan), *operands)
    out = {"shape": list(shape), "dtype": str(jnp.dtype(dtype))}
    for name, call, args in (
            ("hvt_mamba_scan_fwd", scan_op._fwd_call, operands),
            ("hvt_mamba_scan_bwd", scan_op._bwd_call,
             (*operands, entered, y))):
        seconds = []
        for _ in range(2):
            jax.clear_caches()
            t0 = time.perf_counter()
            traced = call.trace(*args, plan=plan)
            t1 = time.perf_counter()
            traced.lower(lowering_platforms=("tpu",))
            seconds.append((t1 - t0, time.perf_counter() - t1))
        out[name] = {"equations": equations(traced.jaxpr.jaxpr),
                     "trace_s": [s[0] for s in seconds],
                     "lower_s": [s[1] for s in seconds]}
    out["equations"] = sum(out[k]["equations"] for k in KERNELS)
    print(json.dumps(out), flush=True)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shape", default=",".join(map(str, SHAPE)),
                        help="batch,seq,channels,state")
    parser.add_argument("--dtypes", default="bfloat16",
                        help="of bfloat16, float32, comma-separated")
    parser.add_argument("--variants", default="derived",
                        help="derived or FWDxBWD, comma-separated")
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--check", type=int, default=512,
                        help="positions held to the recurrence a position "
                        "at a time")
    parser.add_argument("--count", action="store_true",
                        help="no chip: each kernel's equations and the "
                        "seconds to trace and to lower it")
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp

    shape = tuple(int(n) for n in args.shape.split(","))
    if args.count:
        for dtype in args.dtypes.split(","):
            count(shape, jnp.dtype(dtype))
        return
    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/selective_scan.py times the chip: no "
                         "TPU here")
    out = {"device": jax.devices()[0].device_kind, "dtypes": {}}
    for dtype in args.dtypes.split(","):
        out["dtypes"][dtype] = measure(shape, jnp.dtype(dtype),
                                       args.variants.split(","), args.calls,
                                       args.check)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/selective_scan.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
