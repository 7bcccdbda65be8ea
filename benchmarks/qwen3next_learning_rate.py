#!/usr/bin/env python
"""qwen3next_learning_rate.py — which constant learning rate a cell of a
held expert layer (``--cell``: ``qwen3next-s8192`` by default, since PR 40
``lfm2moe-s8192`` and since PR 48 ``kanana2-s8192`` too) can repeat one
batch at: for each of ``--seeds`` and ``--rates`` it
trains the cell's model from the same initialisation on the cell's batch
for ``--steps`` steps and prints, every ``--every`` steps, the loss and
the rows that land on the held experts of each expert layer (a round is
16,384 rows: more than that in a layer is a second round). One compiled
step serves every rate (the rate is part of the optimizer's state,
``optax.inject_hyperparams``).

    chiprun -- python benchmarks/qwen3next_learning_rate.py --rates 1e-6 4e-6
    chiprun -- python benchmarks/qwen3next_learning_rate.py \
        --cell lfm2moe-s8192 --steps 80 --every 20
    chiprun -- python benchmarks/qwen3next_learning_rate.py \
        --cell kanana2-s8192 --steps 0 --seeds 1 2 3   (the rows a fresh
        initialisation gives, seed by seed)

A builder's script: it decides nothing. It refuses to run without a TPU.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rates", type=float, nargs="+",
                   default=[1e-6, 4e-6, 1.6e-5])
    p.add_argument("--steps", type=int, default=48)
    p.add_argument("--every", type=int, default=12)
    p.add_argument("--seeds", type=int, nargs="+", default=[2147488301])
    p.add_argument("--cell", default="qwen3next-s8192")
    args = p.parse_args()

    import importlib

    import jax
    import jax.numpy as jnp
    import optax

    if jax.devices()[0].platform != "tpu":
        sys.exit("qwen3next_learning_rate: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.setup_sources import enable_compile_cache

    enable_compile_cache()
    config, cell, _ = harness.load_cell(args.cell)
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    job = family.build(config, cell)
    cfg = family._model_config(config, cell["seq_len"])
    spec = {k: v for k, v in config["optimizer"].items()
            if k not in ("name", "learning_rate")}
    tx = optax.inject_hyperparams(
        lambda learning_rate: optax.adamw(learning_rate, **spec))(
            learning_rate=0.0)

    @jax.jit
    def init(key, rate):
        params, extra = job.init(key)
        state = tx.init(params)
        state.hyperparams["learning_rate"] = rate
        return params, extra, state

    def step(params, extra, state, batch):
        (loss, extra), grads = jax.value_and_grad(job.loss, has_aux=True)(
            params, extra, batch)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), extra, state, loss

    step = jax.jit(step, donate_argnums=(0, 1, 2))

    @jax.jit
    def rows(params, extra, batch):
        _, sown = job.loss_and_sown(params, extra, batch)
        return [jnp.sum(family.held_rows(block["experts"], cfg))
                for block in sown.values() if "experts" in block]

    make_batch = jax.jit(lambda k: job.make_batch(k, 1))
    for seed, rate in ((s, r) for s in args.seeds for r in args.rates):
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        batch = make_batch(k_batch)
        params, extra, state = init(k_init, rate)
        for i in range(args.steps + 1):
            if i % args.every == 0:
                print(json.dumps({
                    "seed": seed, "learning_rate": rate, "step": i,
                    "rows_on_held_experts": [
                        int(n) for n in rows(params, extra, batch)],
                    # (a batch may be a tree: its largest leaf is the rows)
                    "round": max(int(leaf.size) for leaf in
                                 jax.tree.leaves(batch))}), flush=True)
            if i < args.steps:
                params, extra, state, loss = step(params, extra, state, batch)
                if i % args.every == 0 or i == args.steps - 1:
                    print(json.dumps({"seed": seed, "learning_rate": rate,
                                      "step": i, "loss": float(loss)}),
                          flush=True)
        del params, extra, state


if __name__ == "__main__":
    main()
