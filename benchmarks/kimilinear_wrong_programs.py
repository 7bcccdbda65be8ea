#!/usr/bin/env python
"""kimilinear_wrong_programs.py — what the comparisons of the cell
``kimilinear-s8192`` read for the program as it is, for lower precisions
and for wrong mathematics, on the chip.

    chiprun -- python benchmarks/kimilinear_wrong_programs.py

On the cell's probe (``KELE`` at the published widths and shares, fresh
parameters from a seed) it runs ``chipbench/families/kimi_linear.py``'s own
``check`` (gradients leaf by leaf given the program's experts, the router
against a float32 one on its own input, the two choices of experts, the
Kimi Delta Attention mixer at 8192 positions against the
position-by-position reference twice, as the program ran it and with
float32 products, the latent attention against the reference by query
blocks) first for the package as it is over ``--seeds`` (the margins the
bounds were set from), then once each with: one decay a head in place of
one a channel (a head's mean), no decay, ``beta`` left out, ``silu`` in the
output gate, the gate before the mixer's norm, q and k not L2-normalised, a
convolution of 3 taps (the oldest tap dropped), three lower precisions of
what the configuration states as float32 (the rule's decays, cumulative
sums, inverse and carried state all in bf16, ``kda.STATE_DTYPE``; the
carried state alone, rounded to bf16 after every chunk; the decays alone,
``g`` rounded to bf16 once where the rule takes it), the latent attention
rotated, the routed sum without 2.446, no shared expert, and a router whose
product is left at the TPU's default precision. (On the chip the per-head
norms are the Pallas kernels of ``ops/head_norm.py``; ``l2_norm`` and
``gated_norm`` there are the names the mixer calls them by, so the programs
that swap those names run without the kernel they replace.) Then the loss
of the whole model on a fresh initialisation against the reference's, and
the reference itself at the TPU's default precision: what the step-loss
comparison can and cannot tell. One JSON line each. ``--precision-only``
runs the package as it is and the three lower precisions over every seed
by the mixers' comparisons alone.

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


from benchmarks.qwen3next_wrong_programs import (  # noqa: E402
    _not_normalised, _route_with, _swapped)


def _rule_with(change):
    """``channel_delta_rule`` with ``(g, beta)`` changed."""
    from horovod_tpu.ops import channel_delta_rule as rule_op

    right = rule_op.channel_delta_rule

    def rule(q, k, v, g, beta, **options):
        return right(q, k, v, *change(g, beta), **options)

    return _swapped(rule_op, "channel_delta_rule", rule)


def _as_bf16(x):
    """``x`` rounded to bf16's 8 bits of exponent and 7 of mantissa, in
    ``x``'s dtype. Not a pair of ``astype``: XLA takes a conversion there
    and back out again (``xla_allow_excess_precision``), on the TPU
    without a trace, and the program it then runs is the sound one."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@contextlib.contextmanager
def _state_rounded():
    """``channel_delta_rule`` with the carried state rounded to bf16 after
    every chunk (what a bf16 carry holds) and nothing else changed, in
    both bodies: the kernels round what a chunk hands on by
    ``channel_delta_rule._carried`` (the plan's state dtype, float32 in
    the program), and the plain body's pass calls ``jax.lax.scan`` for its
    carry alone, which rounds the state it hands on while the pass is
    traced. The kernels' calls are jitted by their plan, which this does
    not change: their traces are dropped on the way in and out."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import channel_delta_rule as rule_op
    from horovod_tpu.ops.gated_delta_rule import _rounder

    right = rule_op._one_pass

    def one_pass(*operands, **options):
        real = jax.lax.scan

        def scan(step, state, chunks):
            def rounded(state, chunk):
                state, out = step(state, chunk)
                return _as_bf16(state), out

            return real(rounded, state, chunks)

        jax.lax.scan = scan
        try:
            return right(*operands, **options)
        finally:
            jax.lax.scan = real

    jax.clear_caches()
    try:
        with _swapped(rule_op, "_one_pass", one_pass), _swapped(
                rule_op, "_carried", lambda plan: _rounder(jnp.bfloat16)):
            yield
    finally:
        jax.clear_caches()


def _gate_first(o, z, scale, eps, gate):
    """``head_norm.gated_norm``'s arguments (``o`` and ``z`` ``[b, s, H
    d]``, ``scale [d]``), the gate applied before the norm."""
    import jax
    import jax.numpy as jnp

    heads = lambda t: t.astype(jnp.float32).reshape(
        *t.shape[:-1], -1, scale.shape[-1])
    gated = heads(o) * jax.nn.sigmoid(heads(z))
    return (gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                  + eps) * scale).astype(o.dtype).reshape(
                                      o.shape)


def _without_shared(right):
    """``transformer._expert_layer`` whose layer's output has its shared
    expert taken out again (the tree keeps the shared expert's leaves, so
    the reference reads the same parameters)."""
    import jax
    import jax.numpy as jnp

    def build(cfg):
        layer = right(cfg)

        def call(h):
            out, aux = layer(h)
            p = layer.variables["params"]
            low = h.astype(cfg.dtype)
            w = lambda name: p[name].astype(cfg.dtype)
            shared = jnp.dot(jax.nn.silu(jnp.dot(low, w("shared_gate")))
                             * jnp.dot(low, w("shared_up")), w("shared_down"))
            return out - shared.astype(out.dtype), aux

        return call

    return build


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147488301, 2147488302, 2147488303])
    p.add_argument("--sound-only", action="store_true",
                   help="the package as it is over --seeds, nothing else")
    p.add_argument("--wrong-only", action="store_true",
                   help="the wrong programs on the first seed, nothing else")
    p.add_argument("--precision-only", action="store_true",
                   help="the package as it is and the three programs whose "
                        "float32 parts are bf16, over --seeds, by the "
                        "mixers' comparisons alone (half a minute each "
                        "where the whole check takes two and a half)")
    p.add_argument("--loss-only", action="store_true",
                   help="the whole model's loss on a fresh initialisation "
                        "against the reference's over --seeds, nothing else")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("kimilinear_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import kimi_linear
    from chipbench.reference import kimi_linear as reference
    from chipbench.setup_sources import enable_compile_cache
    from horovod_tpu.models import kda, transformer
    from horovod_tpu.ops import causal_conv, head_norm

    enable_compile_cache()
    config = harness.read_json("chipbench", "configs",
                               "kimi-linear-48b-a3b.json")
    cell = harness.read_json("chipbench", "workloads",
                             "kimilinear-s8192.json")

    def mixers_alone(job, key):
        """``check``'s last part by itself: the same sample, the same
        parameters, the same function."""
        key_sample, key_probe = jax.random.split(key)
        sample = job.make_batch(key_sample, 1)[:kimi_linear.SAMPLE_SEQUENCES]
        params, extra = jax.jit(job.probe.init)(key_probe)
        return job.probe.mixers_close(params, extra, sample, {
            "kda": job.probe.blocks["K"][:1],
            "mla": job.probe.blocks["L"][:1]})

    def readings(label, seed, whole=True):
        """The family's own check, its values parsed from its lines."""
        job = kimi_linear.build(config, cell)   # a fresh trace each time
        out = {"program": label, "seed": seed}
        key = jax.random.key(seed)
        for c in job.check(key) if whole else mixers_alone(job, key):
            found = re.findall(r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+",
                               str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[0]) if found else None}
        print(json.dumps(out), flush=True)

    lower_precisions = (
        ("decays, cumulative sums, inverse and carried state in bf16",
         lambda: _swapped(kda, "STATE_DTYPE", jnp.bfloat16)),
        ("the carried state alone in bf16 (rounded after every chunk)",
         _state_rounded),
        ("the decays alone in bf16 (g rounded; sums, inverse and state "
         "float32)", lambda: _rule_with(lambda g, beta: (_as_bf16(g), beta))))
    if args.precision_only:
        for seed in args.seeds:
            readings("as it is", seed, whole=False)
            for label, wrong in lower_precisions:
                with wrong():
                    readings(label, seed, whole=False)
        return
    for seed in () if args.wrong_only or args.loss_only else args.seeds:
        readings("as it is", seed)
    if args.sound_only:
        return
    seed = args.seeds[0]
    right_config = kimi_linear._model_config
    with_config = lambda **changes: _swapped(
        kimi_linear, "_model_config", lambda config, seq_len:
        dataclasses.replace(right_config(config, seq_len), **changes))
    right_conv = causal_conv.causal_conv
    three_taps = lambda x, weight, bias=None: right_conv(
        x, weight.at[0].set(0.0), bias)
    silu_gate = lambda o, z, w, *, eps, gate: head_norm.gated_norm_plain(
        o, z, w, eps=eps, gate="silu")
    for label, wrong in (
            ("one decay a head (the channels' mean) in place of one a "
             "channel", _rule_with(lambda g, beta: (jnp.broadcast_to(
                 jnp.mean(g, -1, keepdims=True), g.shape), beta))),
            ("the decay left out",
             _rule_with(lambda g, beta: (jnp.zeros_like(g), beta))),
            ("beta left out",
             _rule_with(lambda g, beta: (g, jnp.ones_like(beta)))),
            ("silu in the output gate",
             _swapped(head_norm, "gated_norm", silu_gate)),
            ("the gate before the mixer's norm",
             _swapped(head_norm, "gated_norm", _gate_first)),
            ("q and k not L2-normalised",
             _swapped(head_norm, "l2_norm", _not_normalised)),
            ("a convolution of 3 taps",
             _swapped(causal_conv, "causal_conv", three_taps)),
            *((label, wrong()) for label, wrong in lower_precisions),
            ("the latent attention rotated", with_config(rotary=True)),
            ("the routed sum without 2.446",
             with_config(moe_route_scale=1.0)),
            ("no shared expert", _swapped(
                transformer, "_expert_layer",
                _without_shared(transformer._expert_layer))),
            ("router at the default precision",
             _route_with(lambda o: (o, lambda real, a, b: real(a, b)))),
    ) if not args.loss_only else ():
        with wrong:
            try:
                readings(label, seed)
            except Exception as e:      # a wrong program may not even run
                print(json.dumps({"program": label, "seed": seed,
                                  "raised": repr(e)[:300]}), flush=True)

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch
    job = kimi_linear.build(config, cell)
    for seed in () if args.wrong_only else args.seeds[:2]:
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
