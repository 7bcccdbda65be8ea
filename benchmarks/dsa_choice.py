"""The choice of the ``topk`` best causal keys alone, on the chip:
``ops/dsa.py``'s ``choose`` (``hvt_dsa_choice``) at ``keyevl2-s16384``'s
shape, one sequence of 16,384 positions and ``topk`` 2,048, on float32
index scores the index kernel made (``-inf`` above the diagonal).

What is timed is what step 3 of ``models/dsa.py`` hands step 5: the mask
**and the scores' log-sum-exp over it**. A copy of ``ops/dsa.py`` whose
``choose`` returns the mask alone (``--source``: PR 64's and older) is
given the two XLA passes the loss then made of it, ``logsumexp(where(choice
!= 0, scores, -inf))``, so both sides are timed to the same pair of
results. For each source and each of ``--blocks`` (``ROWSxCHUNK``: rows of
scores a grid step holds and columns a turn of a pass's loop takes;
``derived`` the module's own) device milliseconds a call (the
time chip 0's operations cover in a profiler trace of ``--calls`` calls),
the kernel's own events by name, and beside them the least time the bytes
allow at 819 GB/s as ``dsa_select_roofline`` counts them (the causal
pairs' float32 scores read once and a byte a pair written). Every
source's mask is held to the first's bit for bit, and its log-sum-exp to
``jax.nn.logsumexp`` over that mask.

A microbenchmark: the kernel's own events carry over to the step (PERF.md
section 6, PR 65), XLA's passes only roughly; the cell that decides is
``keyevl2-s16384``.

    chiprun -- python benchmarks/dsa_choice.py \\
        --source _parent/horovod_tpu/ops/dsa.py,horovod_tpu/ops/dsa.py \\
        --blocks derived,128x256,64x1024
"""

import argparse
import importlib.util
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.dirname(os.path.abspath(__file__))]

import causal_conv as bench  # noqa: E402  (beside this file: its timer)

KERNEL = "hvt_dsa_choice"
INDEX_HEADS, INDEX_DIM = 16, 64


def _load(source):
    spec = importlib.util.spec_from_file_location(
        "dsa_under_test_" + str(abs(hash(source))), source)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _pair(dsa, topk):
    """``scores -> (choice, lse_i)`` of one copy of ``ops/dsa.py``."""
    import jax
    import jax.numpy as jnp

    def both(scores):
        out = dsa.choose(scores, topk)
        if isinstance(out, tuple):
            return out
        return out, jax.nn.logsumexp(
            jnp.where(out != 0, scores, -jnp.inf), axis=-1, keepdims=True)

    return jax.jit(both)


def measure(sources, blocks, seq, topk, calls):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.ops import dsa as own

    keys = jax.random.split(jax.random.key(0), 3)
    scores = own.index_scores(
        jax.random.normal(keys[0], (1, seq, INDEX_HEADS, INDEX_DIM),
                          jnp.bfloat16),
        jax.random.normal(keys[1], (1, seq, INDEX_DIM), jnp.bfloat16),
        jax.random.normal(keys[2], (1, seq, INDEX_HEADS), jnp.float32)
        / np.sqrt(INDEX_HEADS * INDEX_DIM))
    pairs = seq * (seq + 1) // 2
    least_ms = 1e3 * pairs * 5 / bench.HBM_BYTES_PER_S
    first = None
    for source in sources:
        dsa = _load(source)
        for block in blocks:
            if block != "derived":
                if not hasattr(dsa, "CHOICE_CHUNK"):
                    continue
                dsa.CHOICE_ROWS, dsa.CHOICE_CHUNK = (
                    int(n) for n in block.split("x"))
            dsa._choice_call.clear_cache()
            fn = _pair(dsa, topk)
            choice, lse_i = jax.block_until_ready(fn(scores))
            if first is None:
                first = choice
                want = jax.nn.logsumexp(jnp.where(
                    first != 0, scores, -jnp.inf), axis=-1, keepdims=True)
            timed = bench._time(fn, (scores,), calls, (KERNEL,))
            print(json.dumps({
                "source": source, "block": block, "seq": seq, "topk": topk,
                **timed, "least_ms": least_ms,
                "kernel_share_of_least": (
                    least_ms / timed[KERNEL] if KERNEL in timed else None),
                "choice_equals_first": bool(jnp.array_equal(choice, first)),
                "lse_i_max_abs_error": float(jnp.max(jnp.abs(lse_i - want))),
                "device": jax.devices()[0].device_kind}), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--source", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "horovod_tpu", "ops", "dsa.py"),
        help="copies of ops/dsa.py joined by ','; the first is the "
             "reference for the mask")
    parser.add_argument("--blocks", default="derived")
    parser.add_argument("--seq", type=int, default=16384)
    parser.add_argument("--topk", type=int, default=2048)
    parser.add_argument("--calls", type=int, default=8)
    args = parser.parse_args(argv)
    measure(args.source.split(","), args.blocks.split(","), args.seq,
            args.topk, args.calls)


if __name__ == "__main__":
    main()
