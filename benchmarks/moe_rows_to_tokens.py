#!/usr/bin/env python
"""moe_rows_to_tokens.py — the movement of a held expert layer's round
that goes from rows to tokens (``models/moe.py``: combine forward,
dispatch backward), alone on the chip: the candidates for the sum of a
round's ``R`` rows into ``[T, width]`` by token.

    chiprun -- python benchmarks/moe_rows_to_tokens.py \
        --tokens 16384 --held 8 --width 1024 --assigned 5632,16384

- ``segment_sum``: what the layer runs (``moe._sum_by_token``): a
  scatter-add of the rows as they come.
- ``segment_sum_sorted``: the rows gathered into token order first, then
  ``jax.ops.segment_sum(..., indices_are_sorted=True)``.
- ``doubling``: no scatter: the rows gathered into token order,
  ``log2(held)`` shifted adds that leave each token's sum at its first
  row, a gather of that row for every token.
- ``slots``: the layer before rounds: the rows padded to ``T x held``
  slots, gathered by the inverse permutation, summed over a token's slots.

and beside them ``plan`` (the sort by token and the integer work the two
sorted candidates need) and ``gather`` (tokens -> rows, the other
direction). For each it prints one JSON line: device ms a call
(chip 0's operations in a profiler trace, loops' container events left
out), wall-clock ms, and the distance from ``slots``' result.

A microbenchmark, not the yardstick: the cell that decides is
``nemotron3s-s8192`` of ``BENCHMARK.json``. It refuses to run without a
TPU.
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
    ap.add_argument("--dtype", default="float32")
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

        return {
            "slots": slots,
            "segment_sum": lambda rows: moe._sum_by_token(rows, token,
                                                          n_tokens),
            "segment_sum_sorted": sorted_sum,
            "doubling": doubling,
            "gather": lambda rows: moe._rows(rows, token),
        }

    for n in (int(x) for x in a.assigned.split(",")):
        keys = jax.random.split(jax.random.key(n), 2)
        chance = n / (n_tokens * held)
        assigned = jax.random.uniform(keys[0], (n_tokens, held)) < chance
        order = jnp.argsort(jnp.where(assigned, jnp.arange(held),
                                      held).reshape(-1), stable=True)
        inverse = jnp.argsort(order)
        count = jnp.minimum(jnp.sum(assigned), n_tokens)
        rows = jax.random.normal(keys[1], (n_tokens, a.width), a.dtype)
        rows = jnp.where((jnp.arange(n_tokens) < count)[:, None], rows, 0)
        token, plan = jax.jit(plan_of)(order, inverse, count)
        fns = {"plan": (jax.jit(plan_of), (order, inverse, count))}
        for name, fn in candidates(token, plan, inverse).items():
            fns[name] = (jax.jit(fn), (rows,))
        want = None
        for name, (fn, args) in fns.items():
            line = {"candidate": name, "tokens": n_tokens, "held": held,
                    "width": a.width, "dtype": a.dtype,
                    "assigned": int(count),
                    "device": jax.devices()[0].device_kind}
            got = jax.block_until_ready(fn(*args))
            if name == "slots":
                want = got
            if name not in ("plan", "gather"):
                line["rel_l2_vs_slots"] = rel_l2(got, want)
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
