#!/usr/bin/env python
"""keyevl2_wrong_programs.py — what the comparisons of the cell
``keyevl2-s16384`` read for the program as it is, for lower precisions and
for wrong mathematics, on the chip.

    chiprun -- python benchmarks/keyevl2_wrong_programs.py

At the probe's size (one sequence of 4,096 positions, so that half the
queries choose; the published widths and shares, fresh parameters from a
seed) it runs ``chipbench/families/keye_vl2.py``'s own ``check``
(gradients leaf by leaf given the program's experts and keys, the router
against a float32 one on its own input, the two choices of experts, the
first and the last mixer's choice against the reference's ``top_k`` and
their output and ``L_I`` against the reference given the program's choice)
first for the package as it is over ``--seeds`` (the margins the bounds
were set from), then once each with: ``topk`` 1,024; no choice at all
(every causal key); a choice without the causal limit (index scores of
every pair, the best 2,048 of the whole row); no ``relu`` in the index
scores; the index scores rounded to bf16 before they are compared; no norm
a head; ``L_I`` dropped; ``pbar`` of one head in place of the 32. Each
replaces one function of ``ops/dsa.py`` or ``models/dsa.py`` at the seam
the mixer calls it through. Then the loss of the whole model at the cell's
16,384 positions on a fresh initialisation against the reference's, and
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

from benchmarks.qwen3next_wrong_programs import _swapped

PROBE_SEQ_LEN = 4096


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
        sys.exit("keyevl2_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import keye_vl2
    from chipbench.reference import keye_vl2 as reference
    from chipbench.setup_sources import enable_compile_cache
    from horovod_tpu.models import dsa as mixer
    from horovod_tpu.ops import dsa as ops

    enable_compile_cache()
    config, cell, _ = harness.load_cell("keyevl2-s16384")
    probe_cell = {**cell, "seq_len": PROBE_SEQ_LEN}
    topk = config["sa_config"]["topk"]

    def readings(label, seed):
        """The family's own check, its values parsed from its lines."""
        job = keye_vl2.build(config, probe_cell)  # a fresh trace each time
        out = {"program": label, "seed": seed}
        for c in job.check(jax.random.key(seed)):
            found = re.findall(r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+",
                               str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[0]) if found else None}
        out["failed"] = [name for name, c in out.items()
                         if isinstance(c, dict) and not c["ok"]]
        print(json.dumps(out), flush=True)

    for seed in () if args.wrong_only or args.loss_only else args.seeds:
        readings("as it is", seed)
    if args.sound_only:
        return

    right = {name: getattr(ops, name) for name in (
        "choose", "index_scores", "index_loss")}

    def every_pair(q_i, k_i, w, relu=True):
        """Index scores of every (query, key) pair, nothing masked."""
        each = jnp.einsum("bqje,bke->bqjk", q_i, k_i,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bqj,bqjk->bqk", w.astype(jnp.float32),
                          jax.nn.relu(each) if relu else each)

    def causal_scores(q_i, k_i, w, relu=True):
        scores = every_pair(q_i, k_i, w, relu)
        s = scores.shape[-1]
        return jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)

    def best_of_the_whole_row(scores, k):
        b, s, _ = scores.shape
        _, index = jax.lax.top_k(scores, min(k, s))
        return jnp.zeros(scores.shape, jnp.int8).at[
            jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
            index].set(1)

    def with_lse(mask_of):
        """``ops.choose``'s pair of a wrong mask: the mask, and the scores'
        log-sum-exp over it for the indexer's loss."""
        def choose(scores, k):
            mask = mask_of(scores, k)
            return mask, jax.nn.logsumexp(
                jnp.where(mask != 0, scores, -jnp.inf), axis=-1,
                keepdims=True)
        return choose

    @contextlib.contextmanager
    def both(*swaps):
        with contextlib.ExitStack() as stack:
            for owner, name, value in swaps:
                stack.enter_context(_swapped(owner, name, value))
            yield

    seed = args.seeds[0]
    for label, wrong in (
            ("topk 1024", both((ops, "choose", lambda scores, k: right[
                "choose"](scores, topk // 2)))),
            ("no choice at all", both((ops, "choose", with_lse(
                lambda scores, k: (~jnp.isneginf(scores)).astype(jnp.int8))))),
            ("a choice without the causal limit", both(
                (ops, "index_scores", every_pair),
                (ops, "choose", with_lse(best_of_the_whole_row)))),
            ("no relu in the index scores", both((
                ops, "index_scores",
                lambda q_i, k_i, w: causal_scores(q_i, k_i, w, relu=False)))),
            ("index scores in bf16", both((
                # (a convert there and back XLA is allowed to take out)
                ops, "index_scores", lambda *parts: jax.lax.reduce_precision(
                    right["index_scores"](*parts), exponent_bits=8,
                    mantissa_bits=7)))),
            ("no norm a head", both((mixer, "head_rms",
                                     lambda x, weight, eps: x))),
            ("L_I dropped", both((
                ops, "index_loss", lambda q, k, lse, q_i, k_i, w, *rest: (
                    jnp.zeros((), jnp.float32),
                    jax.tree.map(jnp.zeros_like, (q_i, k_i, w)))))),
            ("pbar of one head", both((
                ops, "index_loss", lambda q, k, lse, *rest: right[
                    "index_loss"](q[:, :, :1], k[:, :, :1], lse[:, :, :1],
                                  *rest)))),
    ) if not args.loss_only else ():
        with wrong:
            readings(label, seed)

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch
    job = keye_vl2.build(config, cell)
    for seed in () if args.wrong_only else args.seeds[:2]:
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        params, extra = jax.jit(job.init)(k_init)
        batch = jax.jit(lambda k: job.make_batch(k, 1))(k_batch)
        got = float(jax.jit(job.loss)(params, extra, batch)[0])
        want, (ce, index_loss, _) = reference.loss(params, batch, config)
        with _swapped(jax, "default_matmul_precision",
                      lambda name: contextlib.nullcontext()):
            coarse, _ = reference.loss(params, batch, config)
        print(json.dumps({
            "program": "whole model, fresh initialisation", "seed": seed,
            "loss": got, "reference": want, "reference_L_LM": float(ce),
            "reference_L_I": float(index_loss),
            "rel": abs(got - want) / max(abs(want), 1.0),
            "reference_at_default_precision": coarse,
            "its_rel": abs(coarse - want) / max(abs(want), 1.0)}),
            flush=True)
        del params


if __name__ == "__main__":
    main()
