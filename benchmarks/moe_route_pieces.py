#!/usr/bin/env python
"""moe_route_pieces.py — the pieces of an expert layer's routing
(``models/moe.py:moe_route``), each alone on the chip, the way the layer
did them until PR 37 beside the way it does them now and the ways that
were tried and left out.

    chiprun -- python benchmarks/moe_route_pieces.py
    chiprun -- python benchmarks/moe_route_pieces.py --shapes nemotron

Three shapes: ``nemotron`` (``nemotron3s-s8192``: 16,384 tokens of 4096,
512 experts, 22 a token by a sigmoid with a bias, 8 held), ``qwen``
(``qwen3next-s8192``: 16,384 of 2048, 512 experts, 10 a token by a
renormalised softmax, 32 held) and ``olmoe`` (``olmoe-s4096``: 8,192 of
2048, 64 experts, 8 a token, every expert held: its weights are ``[T,
k]``, one a choice, which no ``[T, E]`` mask gives, so only what could
serve it is timed). One JSON line a piece: device ms a call (chip 0's
operations in a profiler trace) and wall-clock ms.

- ``product``: the router's float32 product at ``Precision.HIGHEST``.
- ``score``: the softmax, or the sigmoid and its bias.
- selection: ``top_k`` (``jax.lax.top_k``: values and indices, the parent's
  and still what gives the indices a caller sows); ``kth_kernel`` (the
  ``k``-th largest alone, ``ops/kth_largest.py``, at blocks of 512, 1024
  and 2048 tokens); ``kth_rounds_xla`` (the same ``k`` passes as XLA
  operations, every pass through HBM).
- mask and weights: ``mask_parent`` (the ``[T, k, E]`` comparison of the
  chosen indices with every expert, reduced over ``k``);
  ``mask_kth_cumsum`` (``[T, E]`` comparisons with the ``k``-th score,
  ties by a ``cumsum`` along the experts: what the layer runs),
  ``mask_kth_product`` (ties counted by a product with a triangle of
  ones, exact in bf16 with float32 sums), ``mask_kth_no_ties`` (a
  threshold alone: wrong where scores tie, timed to price the tie-break).
- order: ``order_parent`` (a stable ``argsort`` of the ``T x count``
  slots), ``order_parent_inverse`` (and the ``argsort`` of that, which a
  held layer stopped reading in PR 32), ``order_packed_sort`` (one sort of
  ``key x slots + slot``, no second operand), ``order_scatter`` (each
  assigned slot written to its place, the expert's offset plus the count
  of earlier tokens, by two ``cumsum``s), ``places`` (those two alone).
- ``route_parent`` and ``route_now``: the whole of the route, forward.

A microbenchmark, not the yardstick: the cells that decide are
``nemotron3s-s8192`` and ``qwen3next-s8192`` of ``BENCHMARK.json``. It
refuses to run without a TPU.
"""

import argparse
import json
import os
import sys
import tempfile
import time

SHAPES = {
    # tokens, width, experts, k, held, score
    "nemotron": (16384, 4096, 512, 22, (0, 8), "sigmoid"),
    "qwen": (16384, 2048, 512, 10, (0, 32), "softmax"),
    "olmoe": (8192, 2048, 64, 8, None, "softmax"),
}


def route_parent(h, router, k, *, score, bias, scale, held, renormalise):
    """``moe_route`` as it was until PR 37 (the formulation
    ``tests/test_moe_held.py`` keeps too): ``top_k``, the ``[T, k, E]`` mask,
    a stable ``argsort`` of every slot and the ``argsort`` of that."""
    import jax
    import jax.numpy as jnp

    n_experts = router.shape[-1]
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        probs = pick = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.sigmoid(logits)
        pick = probs + bias
    _, experts = jax.lax.top_k(pick, k)
    chosen = experts[..., None] == jnp.arange(n_experts)
    if held is None:
        weights = jnp.sum(jnp.where(chosen, probs[:, None, :], 0.0), axis=-1)
        counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    else:
        assigned = jnp.any(chosen, axis=1)
        weights = jnp.where(assigned, probs, 0.0)
        counts = jnp.sum(assigned, axis=0, dtype=jnp.int32)
    if renormalise:
        weights = weights * (scale / (
            jnp.sum(weights, axis=-1, keepdims=True) + 1e-20))
    if held is None:
        order = jnp.argsort(experts.reshape(-1), stable=True)
        return weights, order, jnp.argsort(order), counts
    first, count = held
    mine = slice(first, first + count)
    order = jnp.argsort(jnp.where(assigned[:, mine], jnp.arange(count),
                                  count).reshape(-1), stable=True)
    return weights[:, mine], order, counts[mine]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="nemotron,qwen,olmoe")
    ap.add_argument("--iters", type=int, default=10)
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "benchmarks")]
    import jax
    import jax.numpy as jnp
    from moe_rows_to_tokens import device_ms

    from horovod_tpu.models import moe
    from horovod_tpu.ops import kth_largest

    if jax.default_backend() != "tpu":
        raise SystemExit("moe_route_pieces.py times the route on a TPU; "
                         f"found {jax.default_backend()}")

    def timed(name, fn, args, line):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            for _ in range(a.iters):
                out = fn(*args)
            jax.block_until_ready(out)
            wall = (time.perf_counter() - t0) * 1e3 / a.iters
            jax.profiler.stop_trace()
            print(json.dumps({**line, "piece": name, "wall_ms": wall,
                              "device_ms": device_ms(trace_dir, a.iters)}),
                  flush=True)

    for shape in a.shapes.split(","):
        n_tokens, width, n_experts, k, held, score = SHAPES[shape]
        keys = jax.random.split(jax.random.key(n_tokens + k), 3)
        h = jax.random.normal(keys[0], (n_tokens, width), jnp.bfloat16)
        router = 0.02 * jax.random.normal(keys[1], (width, n_experts))
        bias = 0.01 * jax.random.normal(keys[2], (n_experts,))
        options = dict(score=score, bias=bias if score == "sigmoid" else
                       None, scale=2.5, held=held, renormalise=True)
        line = {"shape": shape, "tokens": n_tokens, "experts": n_experts,
                "k": k, "held": held and held[1],
                "device": jax.devices()[0].device_kind}

        def product(h, router):
            return jnp.dot(h.astype(jnp.float32), router,
                           precision=jax.lax.Precision.HIGHEST)

        def scores(logits):
            if score == "softmax":
                return jax.nn.softmax(logits, axis=-1)
            return jax.nn.sigmoid(logits) + bias

        logits = jax.jit(product)(h, router)
        pick = jax.jit(scores)(logits)
        probs = pick if score == "softmax" else pick - bias
        top, experts = jax.lax.top_k(pick, k)
        kth = top[:, -1:]
        timed("product", product, (h, router), line)
        timed("score", scores, (logits,), line)
        timed("top_k", lambda x: jax.lax.top_k(x, k), (pick,), line)

        def kth_rounds_xla(x):
            def level(_, carry):
                bar, found = carry
                below = x < bar
                reached = n_experts - jnp.sum(below, axis=-1, keepdims=True)
                top = jnp.max(jnp.where(below, x, -jnp.inf), axis=-1,
                              keepdims=True)
                return top, jnp.where(reached < k, top, found)

            start = jnp.full((n_tokens, 1), jnp.inf)
            return jax.lax.fori_loop(0, k, level, (start, start))[1]

        timed("kth_rounds_xla", kth_rounds_xla, (pick,), line)
        if kth_largest.serves(n_tokens, n_experts, k):
            for rows in (512, 1024, 2048):
                timed(f"kth_kernel_{rows}",
                      lambda x: kth_largest.kth_largest(x, k, rows=rows),
                      (pick,), line)
            same = bool(jnp.all(kth_largest.kth_largest(pick, k) == kth))
            print(json.dumps({**line, "piece": "kth_kernel",
                              "equals_top_k": same}), flush=True)
        mine = slice(None) if held is None else slice(held[0], sum(held))

        def finish(assigned, probs):
            weights = jnp.where(assigned, probs, 0.0)
            weights = weights * (2.5 / (
                jnp.sum(weights, axis=-1, keepdims=True) + 1e-20))
            return (weights[:, mine],
                    jnp.sum(assigned, axis=0, dtype=jnp.int32)[mine])

        def mask_parent(experts, probs):
            chosen = experts[..., None] == jnp.arange(n_experts)
            if held is not None:
                return finish(jnp.any(chosen, axis=1), probs)
            return (jnp.sum(jnp.where(chosen, probs[:, None, :], 0.0), -1),
                    jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32))

        def mask_kth(in_front_of):
            def mask(pick, kth, probs):
                above, level = pick > kth, pick == kth
                needed = k - jnp.sum(above, axis=-1, keepdims=True,
                                     dtype=jnp.int32)
                return finish(above | (level & (in_front_of(level) < needed)),
                              probs)
            return mask

        triangle = (jnp.arange(n_experts)[:, None]
                    < jnp.arange(n_experts)).astype(jnp.bfloat16)
        timed("mask_parent", mask_parent, (experts, probs), line)
        timed("mask_kth_cumsum", mask_kth(
            lambda level: jnp.cumsum(level, axis=-1, dtype=jnp.int32)
            - level), (pick, kth, probs), line)
        timed("mask_kth_product", mask_kth(
            lambda level: jnp.dot(level.astype(jnp.bfloat16), triangle,
                                  preferred_element_type=jnp.float32)),
            (pick, kth, probs), line)
        timed("mask_kth_no_ties", lambda pick, kth, probs: finish(
            pick >= kth, probs), (pick, kth, probs), line)

        if held is None:
            flat = experts.reshape(-1)
            timed("order_parent", lambda x: jnp.argsort(x, stable=True),
                  (flat,), line)
            timed("order_parent_inverse", lambda x: jnp.argsort(
                jnp.argsort(x, stable=True)), (flat,), line)
        else:
            count = held[1]
            slots = n_tokens * count
            assigned = moe._chosen(pick, k, top)[:, mine]
            key_of = lambda assigned: jnp.where(
                assigned, jnp.arange(count), count).reshape(-1)

            def places(assigned):
                sizes = jnp.sum(assigned, axis=0, dtype=jnp.int32)
                before = jnp.cumsum(assigned, axis=0, dtype=jnp.int32)
                return jnp.where(
                    assigned, jnp.cumsum(sizes) - sizes + before - 1, slots)

            def order_scatter(assigned):
                return jnp.zeros(slots, jnp.int32).at[
                    places(assigned).reshape(-1)].set(
                        jnp.arange(slots, dtype=jnp.int32), mode="drop",
                        unique_indices=True)

            def order_packed_sort(assigned):
                packed = key_of(assigned) * slots + jnp.arange(slots)
                return jnp.sort(packed) % slots

            timed("order_parent", lambda x: jnp.argsort(
                key_of(x), stable=True), (assigned,), line)
            timed("order_parent_inverse", lambda x: jnp.argsort(jnp.argsort(
                key_of(x), stable=True)), (assigned,), line)
            timed("order_packed_sort", order_packed_sort, (assigned,), line)
            timed("order_scatter", order_scatter, (assigned,), line)
            timed("places", places, (assigned,), line)
            want = jnp.argsort(key_of(assigned), stable=True)
            n = int(jnp.sum(assigned))
            print(json.dumps({**line, "piece": "order", "assigned": n, **{
                name: bool(jnp.all(jax.jit(fn)(assigned)[:n] == want[:n]))
                for name, fn in (("packed_sort_equals", order_packed_sort),
                                 ("scatter_equals", order_scatter))}}),
                flush=True)
        timed("route_parent", lambda h, router: route_parent(
            h, router, k, **options), (h, router), line)
        timed("route_now", lambda h, router: tuple(
            x for x in moe.moe_route(h, router, k, **options)[1:5]
            if x is not None), (h, router), line)


if __name__ == "__main__":
    main()
