#!/usr/bin/env python
"""sdar_wrong_programs.py — what the comparisons of the cell ``sdar-s8192``
read for the program as it is, for a lower precision and for wrong
mathematics, on the chip.

    chiprun -- python benchmarks/sdar_wrong_programs.py

It runs ``chipbench/families/sdar.py``'s own comparisons (``check`` on the
probe ``*E*E`` at the published widths and shares, 1,024 data tokens:
gradients leaf by leaf given the program's experts, the router against a
float32 one on its own input, the two choices of experts; ``layers_close``
on the whole model at the cell's 2 x 8,192 rows, which the cell reads on
the window's parameters and this script on a fresh initialisation: the
first and the last attention mixer against the float32 reference by query
blocks, as the step runs them and built again with float32 products, and
the last expert layer given the program's experts; and the whole model's
loss on a fresh initialisation against the reference's) first for the
package as it is over ``--seeds`` (the margins the bounds were set from),
then with a program wrong in one thing on the same seeds: the softmax and
the merge of a noised row's two parts in bf16; the router's product at the
TPU's default precision; a causal mask inside a block; the noised copy
turned at ``L + i``; weights without ``1 / t``; targets shifted by one;
weights not renormalised. The parameter tree stays the package's in every
one, so the reference reads what it always reads. Each must fail at least
one bound (``"failed"`` in its line). One JSON line each.

``--layers-only`` skips the probe; ``--rehearse`` walks the control flow on
the CPU at the family's tiny sizes.

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

QUERY_BLOCK = 256


def _attend_blocks_in_bf16(cfg, q, k, v, core):
    """``transformer._attend_blocks``'s arguments: the rule as a mask, a
    block of query rows at a time, the scores rounded to bf16 and the
    softmax computed in bf16 (which is what a merge in bf16 comes to)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference.sdar import seen

    b, rows, h, d = q.shape
    half, size = rows // 2, cfg.diffusion_block
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    block = min(QUERY_BLOCK, rows)

    @jax.checkpoint
    def queries(args):
        q, at = args                            # [b, block, h, d], [block]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k,
                             preferred_element_type=jnp.float32)
                  * d ** -0.5).astype(jnp.bfloat16)
        probs = jax.nn.softmax(
            jnp.where(seen(at, half, size), scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    out = jax.lax.map(queries, (
        jnp.moveaxis(q.reshape(b, rows // block, block, h, -1), 1, 0),
        jnp.arange(rows).reshape(-1, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, rows, h, -1)


def _causal_inside_a_block():
    """The flash kernels' ``blocks`` dropped from the clean rows' call: a
    clean row sees its own block up to itself alone."""
    from horovod_tpu.ops import flash_attention as fa

    right = fa.flash_attention

    def wrong(q, k, v, **options):
        options.pop("blocks", None)
        return right(q, k, v, **options)

    return _swapped(fa, "flash_attention", wrong)


def _turned_at_l_plus_i():
    """``jnp.tile`` of a copy's positions twice (what ``GPT`` and the
    family make them with) gives ``0 .. 2 L - 1``; every other call of it
    (the rotary's tables) is what it was."""
    import jax.numpy as jnp

    right = jnp.tile

    def wrong(a, n):
        positions = n == 2 and a.ndim == 1 and a.shape[0] > 1 and (
            jnp.issubdtype(a.dtype, jnp.integer))
        return jnp.arange(2 * a.shape[0]) if positions else right(a, n)

    return _swapped(jnp, "tile", wrong)


def _batch_with(change):
    """The family's ``noise_blocks`` with its result changed."""
    from chipbench.families import sdar

    right = sdar.noise_blocks

    def wrong(*args, **kwargs):
        return change(*right(*args, **kwargs))

    return _swapped(sdar, "noise_blocks", wrong)


def wrong_programs():
    """``(label, context manager)`` of every wrong program."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    return (
        ("the softmax and the merge in bf16",
         _swapped(transformer, "_attend_blocks", _attend_blocks_in_bf16)),
        ("the router at the default precision in bf16",
         _route_with(lambda o: (o, lambda real, a, b: real(a, b)))),
        ("a causal mask inside a block", _causal_inside_a_block()),
        ("the noised copy turned at L + i", _turned_at_l_plus_i()),
        ("weights without 1 / t", _batch_with(lambda tokens, targets, w: (
            tokens, targets, (w > 0).astype(jnp.float32)))),
        ("targets shifted by one", _batch_with(lambda tokens, targets, w: (
            tokens, jnp.roll(targets, -1, axis=1), w))),
        ("weights not renormalised",
         _route_with(lambda o: ({**o, "renormalise": False}, None))),
    )


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147600301, 2147600302])
    p.add_argument("--sound-only", action="store_true",
                   help="the package as it is over --seeds, nothing else")
    p.add_argument("--wrong-only", action="store_true",
                   help="the wrong programs over --seeds, nothing else")
    p.add_argument("--layers-only", action="store_true",
                   help="of the comparisons, the whole model's layers and "
                        "loss at the cell's length alone (not the probe's)")
    p.add_argument("--only", nargs="+", metavar="WORD",
                   help="of the wrong programs, those whose label holds one "
                        "of these")
    p.add_argument("--rehearse", action="store_true",
                   help="the family's tiny sizes on whatever is there: "
                        "control flow alone, no reading means anything")
    args = p.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("sdar_wrong_programs: no TPU, nothing to measure")

    from chipbench import compare
    from chipbench import run as harness
    from chipbench.families import sdar
    from chipbench.reference import sdar as reference
    from chipbench.setup_sources import enable_compile_cache

    enable_compile_cache()
    config, cell, _ = harness.load_cell("sdar-s8192")
    if args.rehearse:
        config = {**config, **sdar.REHEARSAL["config"]}
        cell = {**cell, **sdar.REHEARSAL["traffic"]}

    def fresh(job, seed):
        """The whole model on a fresh initialisation and the cell's batch."""
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        params, extra = jax.jit(job.init)(k_init)
        return params, extra, jax.jit(lambda k: job.make_batch(k, 1))(k_batch)

    def readings(label, seed, sound_batch):
        """The family's own check, its comparison of the whole model's
        layers at the cell's length and the whole model's loss against the
        reference's on the batch the package makes (``sound_batch``: a
        wrong program's batch is wrong, the reference's is not), their
        values parsed from their lines."""
        job = sdar.build(config, cell)          # a fresh trace each time
        out = {"program": label, "seed": seed}
        checks = [] if args.layers_only else job.check(jax.random.key(seed))
        params, extra, batch = fresh(job, seed)
        checks += job.layers_close(params, extra, batch)
        got = float(jax.jit(lambda *a: job.loss(*a)[0])(params, extra, batch))
        want, _ = reference.loss(params, sound_batch(seed), config)
        checks.append(compare.close("fresh_loss_vs_reference", got, want,
                                    sdar.LOSS_REL_BOUND, floor=1.0))
        for c in checks:
            found = re.findall(
                r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+", str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[-1 if c.name.startswith(
                               "fresh_loss") else 0]) if found else None}
        out["failed"] = [name for name, c in out.items()
                         if isinstance(c, dict) and not c["ok"]]
        print(json.dumps(out), flush=True)

    sound_job = sdar.build(config, cell)
    batches = {seed: fresh(sound_job, seed)[2] for seed in args.seeds}
    sound_batch = batches.__getitem__
    for seed in () if args.wrong_only else args.seeds:
        readings("as it is", seed, sound_batch)
    if args.sound_only:
        return
    # (a context manager of `wrong_programs` is entered once: a fresh one
    # each time)
    for label in [label for label, _ in wrong_programs()]:
        if args.only and not any(word in label for word in args.only):
            continue
        for seed in args.seeds:
            with contextlib.ExitStack() as stack:
                stack.enter_context(dict(wrong_programs())[label])
                jax.clear_caches()
                readings(label, seed, sound_batch)
    jax.clear_caches()


if __name__ == "__main__":
    main()
