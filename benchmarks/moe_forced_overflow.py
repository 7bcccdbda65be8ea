#!/usr/bin/env python
"""moe_forced_overflow.py — a training step of the cell
``nemotron3s-s8192`` with its routers forced onto the held experts, on
the chip: what the rounds past the first cost when they do run.

    chiprun -- python benchmarks/moe_forced_overflow.py --crowds 0,2,8

The cell's own job and step (``chipbench``'s family and spelling), fresh
parameters from ``--seed``. For each crowd ``c`` the choice bias of every
expert layer is raised on the first ``c`` held experts, so that every
token chooses them: ``c x T`` rows a layer on the held experts, ``c``
rounds of ``models/moe.py`` (0: the routers as they are, 5,600 rows a
layer, one round). The program is compiled once; only the buffers differ.
Prints one JSON line a crowd: wall-clock ms a step over ``--steps`` steps
after two warm-up steps, and the loss. Run from another checkout (the
parent's: ``cd _parent && python ../benchmarks/moe_forced_overflow.py``)
it times that checkout's ``horovod_tpu``.

A builder's script: it decides nothing. It refuses to run without a TPU.
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--crowds", default="0,2,8")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=6)
    a = ap.parse_args(argv)
    import jax
    import horovod_tpu
    from chipbench import run, setup_sources

    if jax.default_backend() != "tpu":
        raise SystemExit("moe_forced_overflow.py times steps on a TPU; "
                         f"found {jax.default_backend()}")
    setup_sources.enable_compile_cache()
    config, cell, _ = run.load_cell("nemotron3s-s8192")
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    job = family.build(config, cell)
    spelled = importlib.import_module(
        f"chipbench.spellings.{cell['spelling']}").build(job, jax.devices()[:1])
    k_init, k_batch = jax.random.split(jax.random.key(a.seed))
    init = jax.jit(job.init)
    batch = jax.jit(lambda k: job.make_batch(k, 1))(k_batch)
    first = config["experts_held_first"]
    shapes = jax.eval_shape(job.init, k_init)
    step = jax.jit(spelled.step, donate_argnums=(0, 1, 2)).lower(
        *shapes, jax.eval_shape(spelled.tx.init, shapes[0]), batch).compile()
    for crowd in (int(c) for c in a.crowds.split(",")):
        params, extra = init(k_init)
        extra = jax.tree.map(       # the choice biases: the buffers [E]
            lambda b: b.at[first:first + crowd].set(10.0), extra)
        state = (params, extra, jax.jit(spelled.tx.init)(params))
        del params, extra
        for i in range(2 + a.steps):
            if i == 2:
                jax.block_until_ready(state)
                t0 = time.perf_counter()
            *state, loss = step(*state, batch)
        jax.block_until_ready(state)
        ms = (time.perf_counter() - t0) * 1e3 / a.steps
        print(json.dumps({
            "crowd": crowd, "step_ms": ms, "loss": float(loss),
            "tokens_a_step": int(batch.size),
            "package": os.path.dirname(horovod_tpu.__file__),
            "device": jax.devices()[0].device_kind}), flush=True)
        del state


if __name__ == "__main__":
    main()
