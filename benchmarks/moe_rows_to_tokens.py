#!/usr/bin/env python
"""moe_rows_to_tokens.py — the movements of a held expert layer's round
(``models/moe.py``) alone on the chip, both directions, each as one pass
over the round's ``T`` rows (what the layer ran until PR 41) beside the
candidates that go by the rows assigned.

    chiprun -- python benchmarks/moe_rows_to_tokens.py \
        --tokens 16384 --held 8 --width 2048 --assigned 8192,10240,16384

Rows to tokens (combine forward, dispatch backward):

- ``segment_sum``: the one pass: rows past the assigned masked, a
  scatter-add of all ``T`` rows as they come, in float32.
- ``kernel``: ``moe._sum_by_token``, what the layer runs: the plan (a
  sort of the round's tokens), the gather of the rows into that order
  and ``ops/sum_by_token.py``'s kernel.
- ``pieces``: the scatter-add of the assigned rows alone, ``piece`` rows
  a trip of a loop the count bounds (XLA; one line a ``--pieces`` size):
  twice a row's cost in the one pass, which XLA sorts by token itself.
- ``combine`` / ``combine_kernel``: the forward sum with what goes with
  it, the mask, the cast to float32 and the weight's product: as passes
  over ``[T, width]`` before one scatter-add onto zeros, and as
  ``moe._add_to_tokens`` onto a total of zeros.
- ``segment_sum_sorted``: the rows gathered into token order first, then
  ``jax.ops.segment_sum(..., indices_are_sorted=True)``.
- ``doubling``: no scatter: the rows gathered into token order,
  ``log2(held)`` shifted adds that leave each token's sum at its first
  row, a gather of that row for every token.
- ``slots``: the layer before rounds: the rows padded to ``T x held``
  slots, gathered by the inverse permutation, summed over a token's slots.

Tokens to rows (dispatch forward, combine backward):

- ``gather``: ``x[token]`` in one pass, what the layer runs
  (``moe._rows_of_tokens``); ``gather_masked``: with the rows past the
  assigned zeroed, as until PR 41; ``gather_pieces``: zeros, filled a
  piece of the assigned rows a trip.
- ``cotangent`` / ``cotangent_pieces``: the float32 cotangent's rows, times
  the weight and cast for the rows' gradient, times the rows and summed
  for the weight's: as passes and as ``moe._add_to_tokens_transposed``.

and ``plan`` (the sort by token and the integer work the two sorted
candidates need). For each it prints one JSON line: device ms a call
(chip 0's operations in a profiler trace, loops' container events left
out), wall-clock ms, and the distance from the one-pass result (``slots``
for the sums that are neither).

A microbenchmark, not the yardstick: the cells that decide are
``lfm2moe-s8192``, ``qwen3next-s8192`` and ``nemotron3s-s8192`` of
``BENCHMARK.json``. It refuses to run without a TPU.
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time


def device_ms(trace_dir, calls):
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    total = 0.0
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                total += sum(e.duration_ns for e in line.events
                             if " while(" not in e.name) / 1e6
    return total / calls


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--assigned", default="5632,16384",
                    help="slots assigned in the round, comma-separated")
    ap.add_argument("--dtype", default="bfloat16",
                    help="the rows' (the layers' are bfloat16; the "
                         "cotangent and the sums are float32 whatever)")
    ap.add_argument("--pieces", default="1024,2048,4096",
                    help="rows a trip of the pieces, comma-separated")
    ap.add_argument("--candidates", default="",
                    help="only these, comma-separated (default: all)")
    ap.add_argument("--iters", type=int, default=10)
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    from chip_smoke import rel_l2
    from horovod_tpu.models import moe

    if jax.default_backend() != "tpu":
        raise SystemExit("moe_rows_to_tokens.py times movements on a TPU; "
                         f"found {jax.default_backend()}")
    n_tokens, held = a.tokens, a.held

    def plan_of(order, inverse, n):
        """The round's tokens, and for the sorted candidates: the
        permutation into token order (the rows past ``n`` last), the keys
        in that order, where each token's run starts, whether it has one."""
        token = order[:n_tokens] // held
        key = jnp.where(jnp.arange(n_tokens) < n, token, n_tokens)
        perm = jnp.argsort(key)
        mine = jnp.sum(inverse.reshape(n_tokens, held) < n, axis=1,
                       dtype=jnp.int32)
        return token, (perm, key[perm], jnp.cumsum(mine) - mine, mine > 0)

    def candidates(token, plan, inverse):
        perm, key, start, has = plan

        def doubling(rows):
            total, shift = moe._rows(rows, perm).astype(jnp.float32), 1
            while shift < held:     # a run of 2 x shift summed at its head
                same = (key[shift:] == key[:-shift])[:, None]
                total = total + jnp.pad(
                    jnp.where(same, total[shift:], 0.0), ((0, shift), (0, 0)))
                shift *= 2
            return jnp.where(has[:, None], moe._rows(total, start),
                             0.0).astype(rows.dtype)

        def sorted_sum(rows):
            return jax.ops.segment_sum(
                moe._rows(rows, perm).astype(jnp.float32), key,
                num_segments=n_tokens + 1,
                indices_are_sorted=True)[:n_tokens].astype(rows.dtype)

        def slots(rows):
            rows = jnp.pad(rows, ((0, n_tokens * (held - 1)), (0, 0)))
            return moe._rows(rows, inverse).reshape(
                n_tokens, held, -1).astype(jnp.float32).sum(1).astype(
                    rows.dtype)

        def masked(rows, n):
            return jnp.where((jnp.arange(n_tokens) < n)[:, None], rows,
                             jnp.zeros((), rows.dtype))

        def combine(rows, weight, n):
            rows = masked(rows, n).astype(jnp.float32) * weight[:, None]
            return jnp.zeros(rows.shape, jnp.float32).at[token].add(
                rows, mode="promise_in_bounds")

        def cotangent(g, rows, weight, n):
            mine = moe._rows(g, token)
            return (masked(mine * weight[:, None], n).astype(rows.dtype),
                    masked(jnp.sum(mine * rows.astype(jnp.float32),
                                   axis=-1)[:, None], n)[:, 0])

        def where(n):
            return token, moe.token_sum.plan(token, n, n_tokens), n

        def scatter_pieces(rows, n):
            def add(at, live, total):
                of = moe._piece_of(at, live)
                return total.at[of(token)].add(
                    jnp.where(live, of(rows).astype(jnp.float32), 0.0),
                    mode="promise_in_bounds")

            return moe._pieces(n, n_tokens, add, jnp.zeros(
                rows.shape, jnp.float32)).astype(rows.dtype)

        def gather_pieces(x, n):
            def take(at, live, rows):
                mine = moe._rows(x, moe._piece_of(at, live)(token))
                return moe._set_piece(rows, at, jnp.where(live, mine, 0))

            return moe._pieces(n, n_tokens, take, jnp.zeros_like(x))

        return {
            "slots": slots,
            "segment_sum": lambda rows, n: jax.ops.segment_sum(
                masked(rows, n).astype(jnp.float32), token,
                num_segments=n_tokens).astype(rows.dtype),
            "kernel": lambda rows, n: moe._sum_by_token(rows, where(n),
                                                        n_tokens),
            "pieces": scatter_pieces,
            "segment_sum_sorted": sorted_sum,
            "doubling": doubling,
            "combine": combine,
            "combine_kernel": lambda rows, weight, n: moe._add_to_tokens(
                jnp.zeros(rows.shape, jnp.float32), rows, weight, where(n)),
            "gather": lambda x, n: moe._rows(x, token),
            "gather_masked": lambda x, n: masked(moe._rows(x, token), n),
            "gather_pieces": gather_pieces,
            "cotangent": cotangent,
            "cotangent_pieces": lambda g, rows, weight, n:
                moe._add_to_tokens_transposed(g, rows, weight, where(n)),
        }

    # what each pieces candidate is held to, and each candidate's operands
    one_pass = {"kernel": "segment_sum", "pieces": "segment_sum",
                "combine_kernel": "combine", "gather_pieces": "gather_masked",
                "cotangent_pieces": "cotangent"}
    no_piece = ("kernel", "combine_kernel")
    only = set(filter(None, a.candidates.split(",")))
    for n in (int(x) for x in a.assigned.split(",")):
        keys = jax.random.split(jax.random.key(n), 4)
        chance = n / (n_tokens * held)
        assigned = jax.random.uniform(keys[0], (n_tokens, held)) < chance
        order = jnp.argsort(jnp.where(assigned, jnp.arange(held),
                                      held).reshape(-1), stable=True)
        inverse = jnp.argsort(order)
        count = jnp.minimum(jnp.sum(assigned), n_tokens)
        rows = jax.random.normal(keys[1], (n_tokens, a.width), a.dtype)
        rows = jnp.where((jnp.arange(n_tokens) < count)[:, None], rows, 0)
        weight = jax.random.uniform(keys[2], (n_tokens,))
        g = jax.random.normal(keys[3], (n_tokens, a.width))
        token, plan = jax.jit(plan_of)(order, inverse, count)
        operands = {"combine": (rows, weight, count),
                    "cotangent": (g, rows, weight, count)}
        runs = [("plan", None, plan_of, (order, inverse, count))]
        for name, fn in candidates(token, plan, inverse).items():
            args = operands.get(name.split("_")[0], (rows, count))
            if name in ("slots", "segment_sum_sorted", "doubling"):
                args = (rows,)
            runs += [(name, piece, fn, args) for piece in (
                map(int, a.pieces.split(","))
                if name in one_pass and name not in no_piece else (None,))]
        want = {}
        for name, piece, fn, args in runs:
            if only and name not in only:
                continue
            line = {"candidate": name, "tokens": n_tokens, "held": held,
                    "width": a.width, "dtype": a.dtype,
                    "assigned": int(count),
                    "device": jax.devices()[0].device_kind}
            if piece:
                moe._PIECE = line["piece"] = piece
            # a function of its own: the piece is read at a trace, and a
            # trace is kept by the function traced
            fn = jax.jit(lambda *x, f=fn: f(*x))
            want[name] = got = jax.block_until_ready(fn(*args))
            against = one_pass.get(name, "slots")
            if against != name and name not in (
                    "plan", "gather", "gather_masked", "combine",
                    "cotangent"):
                if against not in want:
                    want[against] = next(jax.jit(f)(*x) for m, _, f, x in runs
                                         if m == against)
                line[f"rel_l2_vs_{against}"] = max(jax.tree.leaves(
                    jax.tree.map(rel_l2, got, want[against])))
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                t0 = time.perf_counter()
                for _ in range(a.iters):
                    out = fn(*args)
                jax.block_until_ready(out)
                line["wall_ms"] = (time.perf_counter() - t0) * 1e3 / a.iters
                jax.profiler.stop_trace()
                line["device_ms"] = device_ms(trace_dir, a.iters)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
