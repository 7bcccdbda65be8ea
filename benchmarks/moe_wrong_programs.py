#!/usr/bin/env python
"""moe_wrong_programs.py — what the benchmark's comparisons of the cell
``olmoe-s4096`` read for the expert layer as it is, for four wrong
ones, and for its reference in lower precisions, on the chip.

    chiprun -- python benchmarks/moe_wrong_programs.py

On the cell's probe (one layer at OLMoE's published widths, 2 sequences
of 4096 tokens, fresh parameters from a seed) it computes what
``chipbench/families/olmoe.py``'s ``check`` computes: the program's loss
against the float32 reference's, its gradients leaf by leaf against the
reference given the program's expert indices, its router against a
float32 one on its own input, the share of assignments on which the two
choices of experts agree and the largest probability gap a disagreement
overrode. First for ``models/moe.py`` as it is over ``--seeds`` (the
margins the bounds were set from), then with ``moe_route`` replaced by:
a router whose product is left at the TPU's default precision (one bf16
pass), a router multiplied and stored in bf16, top-k weights renormalised
to sum to one, and a capacity of 1.25 x the mean group whose overflow is
dropped. Then the regime in which the cell compares its step's loss:
the probe trained ``--train-steps`` steps on the one sample by the
configuration's optimizer, and on those parameters each program's loss
against the reference's, and the reference itself at the default
precision and on parameters rounded to bf16. One JSON line each.

A builder's script: it decides nothing. It refuses to run without a TPU.
"""

import argparse
import functools
import json
import os
import sys


def _with_dot(right, dot):
    """``right`` with ``jnp.dot`` replaced while it is traced."""
    import jax.numpy as jnp

    def route(h, router, k):
        real = jnp.dot
        jnp.dot = lambda a, b, precision=None: dot(real, a, b)
        try:
            return right(h, router, k)
        finally:
            jnp.dot = real

    return route


def default_precision_router(right):
    """The product without ``precision=HIGHEST``: float32 operands in
    one bf16 pass, float32 sums."""
    return _with_dot(right, lambda real, a, b: real(a, b))


def bf16_router(right):
    import jax.numpy as jnp

    return _with_dot(right, lambda real, a, b: real(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)).astype(jnp.float32))


def renormalised(right):
    def route(h, router, k):
        experts, weights, *rest = right(h, router, k)
        return (experts, weights / weights.sum(-1, keepdims=True), *rest)

    return route


def dropped(right):
    """The parent commit's rule: assignments past 1.25 x the mean group
    lose their weight."""
    import jax.numpy as jnp

    def route(h, router, k):
        experts, weights, order, inverse, sizes, *rest = right(h, router, k)
        capacity = int(1.25 * h.shape[0] * k / router.shape[-1])
        rank = (inverse.reshape(experts.shape)
                - (jnp.cumsum(sizes) - sizes)[experts])
        return (experts, jnp.where(rank < capacity, weights, 0.0), order,
                inverse, sizes, *rest)

    return route


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2147484001,2147484002,2147484003")
    ap.add_argument("--train-steps", type=int, default=150,
                    help="steps of the trained regime (the cell's window "
                         "is about 150); 0 leaves it out")
    a = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    import optax

    if jax.default_backend() != "tpu":
        raise SystemExit("moe_wrong_programs.py compares at published "
                         f"widths on a TPU; found {jax.default_backend()}")
    import horovod_tpu as hvt
    from horovod_tpu.models import moe

    from chipbench.families import olmoe, optimizer_from
    from chipbench.reference import olmoe as reference

    hvt.init()
    with open(os.path.join(root, "chipbench/configs/olmoe-1b-7b.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "chipbench/workloads/olmoe-s4096.json")) as f:
        cell = json.load(f)
    right = moe.moe_route
    k = config["num_experts_per_tok"]
    programs = (("as_it_is", right),
                ("default_precision_router", default_precision_router(right)),
                ("bf16_router", bf16_router(right)),
                ("renormalised_top_k", renormalised(right)),
                ("dropped_assignments", dropped(right)))

    def probe_with(route, differentiate, params, sample):
        """``(loss, routed)`` of the probe with ``route`` for
        ``moe_route``, and its gradients where asked."""
        moe.moe_route = route
        try:
            fn = olmoe.build(config, cell).probe.loss_and_routing
            if differentiate:
                fn = jax.value_and_grad(fn, has_aux=True)
            return jax.jit(fn)(params, sample)
        finally:
            moe.moe_route = right

    def fresh(seed):
        job = olmoe.build(config, cell)
        key_sample, key_probe = jax.random.split(jax.random.key(seed))
        sample = job.make_batch(key_sample, 1)[:olmoe.SAMPLE_SEQUENCES]
        return jax.jit(job.probe.init)(key_probe)[0], sample

    def one(name, route, seed):
        params, sample = fresh(seed)
        (loss, routed), got = probe_with(route, True, params, sample)
        choices = [r["experts"] for r in routed]
        (_, routing), want = reference.loss_and_grad(
            params, sample, config, choices)
        own_loss, _ = reference.loss(params, sample, config)
        agree, gap = zip(*(olmoe.compare_choices(c, r["probs"], r["own"])
                           for c, r in zip(choices, routing)))
        print(json.dumps({
            "regime": "fresh", "program": name, "seed": seed,
            "loss": float(loss), "reference_loss": own_loss,
            "loss_rel": abs(float(loss) - own_loss) / max(abs(own_loss), 1),
            "gradients": olmoe.worst_leaf_close(
                "grad", got, want, olmoe.GRAD_REL_L2_BOUND).line(),
            "router_distance": [
                olmoe.router_distance(
                    r, params[f"block_{i}"]["moe"]["router"], k)
                for i, r in enumerate(routed)],
            "experts_agree": agree, "largest_gap_overridden": gap,
            "largest_group_over_mean": [
                olmoe.load(c, config["num_experts"]) for c in choices]}),
            flush=True)

    def trained(seed, steps):
        """The cell's own regime: the one sample learnt by heart."""
        params, sample = fresh(seed)
        tx = optimizer_from(config["optimizer"])
        loss_of = olmoe.build(config, cell).probe.loss

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state):
            (loss, _), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, {}, sample)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        opt_state = jax.jit(tx.init)(params)
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state)
        del opt_state
        want, _ = reference.loss(params, sample, config)
        apart = lambda got: abs(float(got) - want) / max(abs(want), 1)
        line = lambda what, got: print(json.dumps({
            "regime": f"trained {steps} steps", "what": what, "seed": seed,
            "loss": float(got), "reference_loss": want,
            "loss_rel": apart(got)}), flush=True)
        line("last training step", loss)
        for name, route in programs:
            line(name, probe_with(route, False, params, sample)[0])
        plain = jax.jit(lambda p, t: reference.total(
            config, *reference.parts(p, t, config)[:3]))
        line("reference at the default matmul precision",
             plain(params, sample))
        with jax.default_matmul_precision("highest"):
            line("reference on parameters rounded to bf16", plain(
                jax.tree.map(lambda w: w.astype(jnp.bfloat16).astype(
                    jnp.float32), params), sample))

    seeds = [int(s) for s in a.seeds.split(",")]
    for seed in seeds:
        one("as_it_is", right, seed)
    for name, wrong in programs[1:]:
        one(name, wrong, seeds[0])
    if a.train_steps:
        for seed in seeds[:2]:
            trained(seed, a.train_steps)


if __name__ == "__main__":
    main()
