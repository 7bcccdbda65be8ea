#!/usr/bin/env python
"""lfm2_wrong_programs.py — what the comparisons of the cell
``lfm2moe-s8192`` read for the program as it is, for lower precisions and
for wrong mathematics, on the chip.

    chiprun -- python benchmarks/lfm2_wrong_programs.py

On the cell's probe (``C-*ECE`` at the published widths and shares, fresh
parameters from a seed) it runs ``chipbench/families/lfm2_moe.py``'s own
``check`` (gradients leaf by leaf given the program's experts, the routers
against a float32 one on their own input, the two choices of experts, the
short-convolution mixer at 8192 positions against the position-by-position
reference) first for the package as it is over ``--seeds`` (the margins
the bounds were set from), then once each with: the mixer's gates and taps
in bf16, every RMSNorm in bf16, a router whose product is left at the
TPU's default precision (one bf16 pass), the chosen weights not
renormalised, the gate ``B`` left out, and the rotary at base 1e4. Then
the loss of the whole model on a fresh initialisation against the
reference's, and the reference itself at the TPU's default precision: what
the step-loss comparison can and cannot tell. One JSON line each.

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

from benchmarks.qwen3next_wrong_programs import _route_with, _swapped


def _bf16(x):
    """Rounded to bf16's 8 bits of mantissa whatever XLA keeps it in (a
    conversion to bf16 and straight back is dropped)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _gated_conv_bf16(b, c, u, weight):
    """``sconv.gated_conv``'s arguments, every product and sum rounded to
    bf16 as a bf16 module would."""
    import jax.numpy as jnp

    taps, seq = weight.shape[0], u.shape[1]
    f32 = lambda t: t.astype(jnp.float32)
    padded = jnp.pad(_bf16(f32(b) * f32(u)), ((0, 0), (taps - 1, 0), (0, 0)))
    conv = 0.0
    for j in range(taps):
        conv = _bf16(conv + _bf16(_bf16(f32(weight[j]))
                                  * padded[:, j:j + seq]))
    return _bf16(f32(c) * conv).astype(u.dtype)


def _bf16_norm():
    """``transformer.RMSNorm`` with its mean square, root and scale in
    bf16."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class RMSNorm(nn.Module):
        eps: float = 1e-6
        unit_offset: bool = False

        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones_init(),
                               (x.shape[-1],), jnp.float32)
            x32 = x.astype(jnp.float32)
            var = _bf16(jnp.mean(_bf16(x32 * x32), axis=-1, keepdims=True))
            return _bf16(_bf16(x32 * _bf16(jax.lax.rsqrt(var + self.eps)))
                         * _bf16(scale)).astype(x.dtype)

    return RMSNorm


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147500301, 2147500302, 2147500303])
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
        sys.exit("lfm2_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import lfm2_moe
    from chipbench.reference import lfm2_moe as reference
    from chipbench.setup_sources import enable_compile_cache
    from horovod_tpu.models import sconv, transformer

    enable_compile_cache()
    config, cell, _ = harness.load_cell("lfm2moe-s8192")

    def readings(label, seed):
        """The family's own check, its values parsed from its lines."""
        job = lfm2_moe.build(config, cell)      # a fresh trace each time
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
    right_config, right_gated_conv = lfm2_moe._model_config, sconv.gated_conv
    without_b = lambda b, c, u, weight: right_gated_conv(
        jnp.ones_like(b), c, u, weight)
    short_base = lambda config, seq_len: dataclasses.replace(
        right_config(config, seq_len), rotary_base=1e4)
    for label, wrong in (
            ("the mixer's gates and taps in bf16",
             _swapped(sconv, "gated_conv", _gated_conv_bf16)),
            ("every RMSNorm in bf16",
             _swapped(transformer, "RMSNorm", _bf16_norm())),
            ("router at the default precision",
             _route_with(lambda o: (o, lambda real, a, b: real(a, b)))),
            ("chosen weights not renormalised",
             _route_with(lambda o: ({**o, "renormalise": False}, None))),
            ("the gate B left out",
             _swapped(sconv, "gated_conv", without_b)),
            ("the rotary at base 1e4",
             _swapped(lfm2_moe, "_model_config", short_base))
    ) if not args.loss_only else ():
        with wrong:
            readings(label, seed)

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch
    job = lfm2_moe.build(config, cell)
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
