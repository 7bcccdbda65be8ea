#!/usr/bin/env python
"""qwen3next_wrong_programs.py — what the comparisons of the cell
``qwen3next-s8192`` read for the program as it is, for lower precisions
and for wrong mathematics, on the chip.

    chiprun -- python benchmarks/qwen3next_wrong_programs.py

On the cell's probe (``G*E`` at the published widths and shares, fresh
parameters from a seed) it runs ``chipbench/families/qwen3_next.py``'s own
``check`` (gradients leaf by leaf given the program's experts, the router
against a float32 one on its own input, the two choices of experts, the
Gated DeltaNet mixer at 8192 positions against the position-by-position
reference) first for the package as it is over ``--seeds`` (the margins
the bounds were set from), then once each with: the rule's decays,
cumulative sums, inverse and carried state in bf16 (``gdn.STATE_DTYPE``),
a router whose product is left at the TPU's default precision (one bf16
pass), the chosen weights not renormalised, q and k not L2-normalised,
``beta`` left out, the decay left out, the gate applied before the
mixer's norm, and the rotary over the whole head. (On the chip the per-head
norms are the Pallas kernels of ``ops/head_norm.py``; ``l2_norm`` and
``gated_norm`` there are the names the mixer calls them by, so the
two programs that swap those names run without the kernel they replace:
the swap steers the kernel path.) Then the loss of the
whole model on a fresh initialisation against the reference's, and the
reference itself at the TPU's default precision: what the step-loss
comparison can and cannot tell. One JSON line each.

A builder's script: it decides nothing. It refuses to run without a TPU.
"""

import argparse
import contextlib
import dataclasses
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


def _route_with(change):
    """``moe_route`` with its options, or ``jnp.dot`` while it is traced,
    changed by ``change(options) -> (options, dot or None)``."""
    import jax.numpy as jnp

    from horovod_tpu.models import moe

    right = moe.moe_route

    def route(h, router, k, **options):
        options, dot = change(options)
        real = jnp.dot
        if dot is not None:
            jnp.dot = lambda a, b, precision=None: dot(real, a, b)
        try:
            return right(h, router, k, **options)
        finally:
            jnp.dot = real

    return _swapped(moe, "moe_route", route)


def _rule_given(**fixed):
    """``gated_delta_rule`` with ``g`` or ``beta`` replaced by a
    constant."""
    import jax.numpy as jnp

    from horovod_tpu.ops import gated_delta_rule as rule_op

    right = rule_op.gated_delta_rule

    def rule(q, k, v, g, beta, **options):
        given = dict(g=g, beta=beta)
        given.update({name: jnp.full_like(given[name], value)
                      for name, value in fixed.items()})
        return right(q, k, v, given["g"], given["beta"], **options)

    return _swapped(rule_op, "gated_delta_rule", rule)


def _gate_first(o, z, scale, eps):
    """``head_norm.gated_norm``'s arguments (``o`` and ``z`` ``[b, s, H
    d]``, ``scale [d]``), the gate applied before the norm."""
    import jax
    import jax.numpy as jnp

    heads = lambda t: t.astype(jnp.float32).reshape(
        *t.shape[:-1], -1, scale.shape[-1])
    gated = heads(o) * jax.nn.silu(heads(z))
    return (gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                  + eps) * scale).astype(o.dtype).reshape(
                                      o.shape)


def _not_normalised(x, dim=None, eps=None, scale=1.0):
    """``head_norm.l2_norm``'s arguments, the norm left out."""
    import jax.numpy as jnp

    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147488301, 2147488302, 2147488303])
    p.add_argument("--sound-only", action="store_true",
                   help="the package as it is over --seeds, nothing else")
    p.add_argument("--wrong-only", action="store_true",
                   help="the wrong programs on the first seed, nothing else")
    p.add_argument("--loss-only", action="store_true",
                   help="the whole model's loss on a fresh initialisation "
                        "against the reference's over --seeds, nothing else")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("qwen3next_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import qwen3_next
    from chipbench.reference import qwen3_next as reference
    from chipbench.setup_sources import enable_compile_cache
    from horovod_tpu.models import gdn
    from horovod_tpu.ops import head_norm

    enable_compile_cache()
    config = harness.read_json("chipbench", "configs", "qwen3-next-80b.json")
    cell = harness.read_json("chipbench", "workloads", "qwen3next-s8192.json")

    def readings(label, seed):
        """The family's own check, its values parsed from its lines."""
        job = qwen3_next.build(config, cell)    # a fresh trace each time
        out = {"program": label, "seed": seed}
        for c in job.check(jax.random.key(seed)):
            found = re.findall(r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+",
                               str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[0]) if found else None}
        print(json.dumps(out), flush=True)

    for seed in () if args.wrong_only or args.loss_only else args.seeds:
        readings("as it is", seed)
    if args.sound_only:
        return
    seed = args.seeds[0]
    right_config = qwen3_next._model_config
    whole_head = lambda config, seq_len: dataclasses.replace(
        right_config(config, seq_len), rotary_fraction=1.0)
    for label, wrong in (
            ("decays, cumulative sums, inverse and carried state in bf16",
             _swapped(gdn, "STATE_DTYPE", jnp.bfloat16)),
            ("router at the default precision",
             _route_with(lambda o: (o, lambda real, a, b: real(a, b)))),
            ("chosen weights not renormalised",
             _route_with(lambda o: ({**o, "renormalise": False}, None))),
            ("q and k not L2-normalised",
             _swapped(head_norm, "l2_norm", _not_normalised)),
            ("beta left out", _rule_given(beta=1.0)),
            ("the decay left out", _rule_given(g=0.0)),
            ("the gate before the mixer's norm",
             _swapped(head_norm, "gated_norm", _gate_first)),
            ("the rotary over the whole head",
             _swapped(qwen3_next, "_model_config", whole_head))
    ) if not args.loss_only else ():
        with wrong:
            readings(label, seed)

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch
    job = qwen3_next.build(config, cell)
    for seed in () if args.wrong_only else args.seeds[:2]:
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        params, extra = jax.jit(job.init)(k_init)
        batch = jax.jit(lambda k: job.make_batch(k, 1))(k_batch)
        got = float(jax.jit(job.loss)(params, extra, batch)[0])
        want, _ = reference.loss(params, batch, config)
        with _swapped(jax, "default_matmul_precision",
                      lambda name: contextlib.nullcontext()):
            coarse, _ = reference.loss(params, batch, config)
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
