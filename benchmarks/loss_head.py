"""The chunked loss alone, on the chip: ``jax.value_and_grad`` of
``ops.losses.softmax_cross_entropy_fused`` for the hidden states and the
matrix, at the ``(batch, positions, d, V, chunk)`` at which the four sparse
and hybrid cells of ``BENCHMARK.json`` call it (bf16 hidden states, a
float32 matrix, the families' ``LOSS_CHUNK``), and beside it the unchunked
computation (the whole ``[batch, positions, V]`` float32 logits and
``optax``'s cross-entropy under autodiff: three products and no loop), which
is the yardstick where it fits the chip.

A JSON line a path: device milliseconds a call (the time chip 0's operations
cover in a profiler trace of ``--calls`` calls, over the calls; a ``while``
event and its body counted once), wall-clock milliseconds a call, and beside
them the least time the three products the mathematics needs take at
``chipbench/peaks.json``'s 197 TFLOP/s (``3 x 2 T d V``) and how close the
path came. ``--source FILE`` times another copy of ``ops/losses.py`` (the
parent's: ``--source _parent/horovod_tpu/ops/losses.py``), which until PR 42
made four products a chunk in two loops.

A microbenchmark: the loss alone is not its cost inside the step, where the
matrix's gradient meets the optimizer; the cells that decide are
``olmoe-s4096``, ``nemotron3s-s8192``, ``qwen3next-s8192`` and
``lfm2moe-s8192``.

    chiprun -- python benchmarks/loss_head.py
"""

import argparse
import importlib.util
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.dirname(os.path.abspath(__file__))]

import causal_conv as bench  # noqa: E402  (beside this file: its timer)

PEAK_FLOPS = 197e12
# cell: batch, positions (the sequence less the last), d, V, LOSS_CHUNK
SHAPES = {"olmoe-s4096": (2, 4095, 2048, 50304, 512),
          "nemotron3s-s8192": (2, 8191, 4096, 16384, 1024),
          "qwen3next-s8192": (2, 8191, 2048, 18992, 1024),
          "lfm2moe-s8192": (2, 8191, 2048, 8192, 1024)}


def load_loss(source):
    if source is None:
        from horovod_tpu.ops.losses import softmax_cross_entropy_fused
        return softmax_cross_entropy_fused
    spec = importlib.util.spec_from_file_location("losses_under_test", source)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.softmax_cross_entropy_fused


def unchunked(hidden, emb, targets):
    import jax.numpy as jnp
    import optax

    logits = jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                        emb.astype(jnp.float32))
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, targets).mean()


def measure(cell, chunked, calls, with_unchunked):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chip_smoke import rel_l2

    b, s, d, v, chunk = SHAPES[cell]
    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.normal(size=(b, s, d)), jnp.bfloat16)
    emb = jnp.asarray(rng.normal(size=(v, d)) * 0.02, jnp.float32)
    targets = jnp.asarray(rng.randint(0, v, (b, s)), jnp.int32)
    least = 1e3 * 3 * 2 * b * s * d * v / PEAK_FLOPS
    paths = [("chunked", lambda h, e: chunked(h, e, targets, chunk=chunk))]
    if with_unchunked:
        paths.append(("unchunked", lambda h, e: unchunked(h, e, targets)))
    first = None
    for name, loss in paths:
        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        line = {"cell": cell, "path": name, "tokens": b * s, "d": d,
                "vocab": v, "chunk": chunk if name == "chunked" else None,
                "least_ms_three_products": least}
        try:
            got = jax.device_get(both(hidden, emb))
        except Exception as e:      # the yardstick does not fit: say so
            line["refused"] = str(e).split("\n")[0][-300:]
            print(json.dumps(line), flush=True)
            continue
        first = got if first is None else first
        timed = bench._time(both, (hidden, emb), calls, ())
        line.update(loss=float(got[0]), rel_l2_vs_chunked=rel_l2(got, first),
                    share_of_peak=least / timed["device_ms"], **timed)
        print(json.dumps(line), flush=True)
        yield line


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", default=",".join(SHAPES),
                        help="of " + ", ".join(SHAPES))
    parser.add_argument("--source", help="another copy of ops/losses.py")
    parser.add_argument("--no-unchunked", action="store_true")
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/loss_head.py times the chip: no TPU "
                         "here")
    chunked = load_loss(args.source)
    out = {"device": jax.devices()[0].device_kind,
           "source": args.source or "horovod_tpu/ops/losses.py", "lines": []}
    for cell in args.cells.split(","):
        out["lines"] += measure(cell, chunked, args.calls,
                                not args.no_unchunked)
    os.makedirs("chiprun_out", exist_ok=True)
    tag = "parent" if args.source else "change"
    with open(f"chiprun_out/loss_head_{tag}.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
