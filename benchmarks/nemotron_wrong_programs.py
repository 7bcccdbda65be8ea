#!/usr/bin/env python
"""nemotron_wrong_programs.py — what the comparisons of the cell
``nemotron3s-s8192`` read for the program as it is, for lower precisions
and for wrong mathematics, on the chip.

    chiprun -- python benchmarks/nemotron_wrong_programs.py

On the cell's probe (``*EM`` at the published widths and shares, fresh
parameters from a seed) it runs ``chipbench/families/nemotron_h.py``'s
own ``check`` (gradients leaf by leaf given the program's experts, the
router against a float32 one on its own input, the two choices of
experts, the Mamba-2 mixer at 8192 positions against the
position-by-position reference) first for the package as it is over
``--seeds`` (the margins the bounds were set from), then once each with:
the scan's decays, cumulative sums and carried state in bf16
(``ssm.DECAY_DTYPE``), a router whose product is left at the TPU's
default precision (one bf16 pass), a router multiplied in bf16, and
routing weights without ``routed_scaling_factor``. Then the loss of the whole
11-layer model on a fresh initialisation against the reference's, and
the reference itself at the TPU's default precision: what the step-loss
comparison can and cannot tell. One JSON line each.

A builder's script: it decides nothing. It refuses to run without a TPU.
"""

import argparse
import contextlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@contextlib.contextmanager
def _swapped(owner, name, value):
    was = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, was)


def _router_dot(cast):
    """``moe_route`` with its logits' product replaced while traced."""
    import jax.numpy as jnp

    from horovod_tpu.models import moe

    right = moe.moe_route

    def route(h, router, k, **options):
        real = jnp.dot
        jnp.dot = lambda a, b, precision=None: cast(real, a, b)
        try:
            return right(h, router, k, **options)
        finally:
            jnp.dot = real

    return _swapped(moe, "moe_route", route)


def _not_scaled():
    """``moe_route`` without ``routed_scaling_factor``: wrong
    mathematics, not a precision."""
    from horovod_tpu.models import moe

    right = moe.moe_route

    def route(h, router, k, **options):
        return right(h, router, k, **{**options, "scale": 1.0})

    return _swapped(moe, "moe_route", route)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147488201, 2147488202, 2147488203])
    p.add_argument("--sound-only", action="store_true",
                   help="the package as it is over --seeds, nothing else")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("nemotron_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import nemotron_h
    from chipbench.reference import nemotron_h as reference
    from horovod_tpu.models import ssm

    config = harness.read_json("chipbench", "configs",
                               "nemotron3-super-120b.json")
    cell = harness.read_json("chipbench", "workloads",
                             "nemotron3s-s8192.json")

    def readings(label, seed):
        """The family's own check, its values parsed from its lines."""
        job = nemotron_h.build(config, cell)    # a fresh trace each time
        out = {"program": label, "seed": seed}
        for c in job.check(jax.random.key(seed)):
            found = re.findall(r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+",
                               str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[0]) if found else None}
        print(json.dumps(out), flush=True)

    for seed in args.seeds:
        readings("as it is", seed)
    if args.sound_only:
        return
    seed = args.seeds[0]
    with _swapped(ssm, "DECAY_DTYPE", jnp.bfloat16):
        readings("decays, cumulative sums and carried state in bf16", seed)
    with _router_dot(lambda real, a, b: real(a, b)):
        readings("router at the default precision", seed)
    with _router_dot(lambda real, a, b: real(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)).astype(
                jnp.float32)):
        readings("router in bf16", seed)
    with _not_scaled():
        readings("weights without routed_scaling_factor", seed)

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch
    job = nemotron_h.build(config, cell)
    for seed in args.seeds[:2]:
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        params, extra = jax.jit(job.init)(k_init)
        batch = jax.jit(lambda k: job.make_batch(k, 1))(k_batch)
        got = float(jax.jit(job.loss)(params, extra, batch)[0])
        want, _ = reference.loss(params, extra["buffers"], batch, config)
        with _swapped(jax, "default_matmul_precision",
                      lambda name: contextlib.nullcontext()):
            coarse, _ = reference.loss(params, extra["buffers"], batch,
                                       config)
        print(json.dumps({
            "program": "whole model, fresh initialisation", "seed": seed,
            "loss": got, "reference": want,
            "rel": abs(got - want) / max(abs(want), 1.0),
            "reference_at_default_precision": coarse,
            "its_rel": abs(coarse - want) / max(abs(want), 1.0)}),
            flush=True)
        del params


if __name__ == "__main__":
    main()
