#!/usr/bin/env python
"""kanana2_wrong_programs.py — what the comparisons of the cell
``kanana2-s8192`` read for the program as it is, for lower precisions and
for wrong mathematics, on the chip.

    chiprun -- python benchmarks/kanana2_wrong_programs.py

On the cell's probe (``L-LE`` at the published widths and shares, fresh
parameters from a seed) it runs ``chipbench/families/deepseek_v3.py``'s
own ``check`` (gradients leaf by leaf given the program's experts, the
router against a float32 one on its own input, the two choices of experts,
the latent-attention mixer at 8192 positions against the float32 reference
by query blocks) first for the package as it is over ``--seeds`` (the
margins the bounds were set from), then once each with: the latent's norm
left out, the rotary on halves where the reference has the published
pairs (the weights' columns not regrouped), the rotated key taken a head
instead of shared (head ``h`` reads it rolled by ``h`` channels), the scale
at ``128^-1/2``, the route scale at 1, the chosen weights not
renormalised, a router whose product is left at the TPU's default
precision (one bf16 pass), and the softmax in bf16 (the last and the key a
head both replace ``mla.causal_attention``, the seam at which the mixer
hands the kernels its four parts since PR 49: on the chip ``whole_key`` is
no longer called). Then the loss of the
whole model on a fresh initialisation against the reference's, and the
reference itself at the TPU's default precision: what the step-loss
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

from benchmarks.qwen3next_wrong_programs import _route_with, _swapped

# Queries whose bf16 scores against every key the wrong softmax holds at
# once.
QUERY_BLOCK = 512


def _attention_with_a_key_a_head(q_n, q_r, k_n, k_r, v, positions, scale,
                                 use_flash):
    """``mla.causal_attention``'s arguments, the rotated key no longer
    shared: head ``h`` reads it rolled by ``h`` channels. The kernels take
    one ``k_r`` a position, so a key a head goes to them assembled, at one
    width (what the mixer ran until PR 49)."""
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as flash

    rolled = jnp.stack([jnp.roll(k_r, h, axis=-1)
                        for h in range(k_n.shape[2])], axis=2)
    return flash.flash_attention(
        jnp.concatenate([q_n, q_r], axis=-1),
        jnp.concatenate([k_n, rolled], axis=-1), v, causal=True, scale=scale)


def _attention_with_a_bf16_softmax(q_n, q_r, k_n, k_r, v, positions, scale,
                                   use_flash):
    """``mla.causal_attention``'s arguments: the scores rounded to bf16 and
    the softmax computed in bf16, a block of queries at a time, on the
    query and the key assembled whole."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import mla

    q, k = jnp.concatenate([q_n, q_r], axis=-1), mla.whole_key(k_n, k_r)
    b, s, h, _ = q.shape
    block = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def queries(args):
        q, at = args                            # [b, block, h, d], [block]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k,
                             preferred_element_type=jnp.float32)
                  * scale).astype(jnp.bfloat16)
        seen = jnp.arange(s)[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    out = jax.lax.map(queries, (
        jnp.moveaxis(q.reshape(b, s // block, block, h, -1), 1, 0),
        jnp.arange(s).reshape(-1, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, -1)


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

    if jax.devices()[0].platform != "tpu":
        sys.exit("kanana2_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import deepseek_v3
    from chipbench.reference import deepseek_v3 as reference
    from chipbench.setup_sources import enable_compile_cache
    from horovod_tpu.models import mla

    enable_compile_cache()
    config, cell, _ = harness.load_cell("kanana2-s8192")

    def readings(label, seed):
        """The family's own check, its values parsed from its lines."""
        job = deepseek_v3.build(config, cell)   # a fresh trace each time
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
    for label, wrong in (
            ("the latent's norm left out",
             _swapped(mla, "latent_norm", lambda c, weight, eps: c)),
            ("the rotary on halves without the permutation",
             _swapped(mla, "pairs_to_halves", lambda w, width: w)),
            ("the rotated key taken a head instead of shared",
             _swapped(mla, "causal_attention",
                      _attention_with_a_key_a_head)),
            ("the scale at 128^-1/2",
             _swapped(mla, "score_scale", lambda nope, rope: nope ** -0.5)),
            ("the route scale at 1",
             _route_with(lambda o: ({**o, "scale": 1.0}, None))),
            ("chosen weights not renormalised",
             _route_with(lambda o: ({**o, "renormalise": False}, None))),
            ("router at the default precision",
             _route_with(lambda o: (o, lambda real, a, b: real(a, b)))),
            ("the softmax in bf16",
             _swapped(mla, "causal_attention",
                      _attention_with_a_bf16_softmax))
    ) if not args.loss_only else ():
        with wrong:
            readings(label, seed)

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch
    job = deepseek_v3.build(config, cell)
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
