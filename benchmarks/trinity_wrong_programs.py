#!/usr/bin/env python
"""trinity_wrong_programs.py — what the comparisons of the cell
``trinitymini-s16384`` read for the program as it is, for a lower precision
and for wrong mathematics, on the chip.

    chiprun -- python benchmarks/trinity_wrong_programs.py

It runs ``chipbench/families/afmoe.py``'s own comparisons (``check`` on the
probe ``W-WE*E`` at the published widths and shares, 4,096 positions:
gradients leaf by leaf given the program's experts, the router against a
float32 one on its own input, the two choices of experts; and
``mixers_close`` on the whole model at 16,384 positions, which the cell
reads on the window's parameters and this script on a fresh
initialisation: the first and the last windowed mixer and the first full
one against the float32 reference by query blocks, as the step runs them
and built again with float32 products) first for the package as it is over
``--seeds`` (the margins the bounds were set from), then with a program
wrong in one thing, a lower precision on every seed and wrong mathematics
on the first: a window of 2,047; of 2,049; no window; a window in the full
layers too; the full layers turned; the windowed layers not turned; no
gate; ``silu`` in the gate; no norm a head; the second norm left out; the
second norm after the residual sum; the embedding unscaled; the routed sum
without 2.826; no shared expert; the softmax in bf16. The parameter tree
stays the package's in every one, so the reference reads what it always
reads. Then the loss of the whole model on a fresh initialisation against
the reference's, for the package, for the reference itself at the TPU's
default precision and for three of the wrong programs (what the step-loss
comparison can and cannot tell), and the flash kernels alone at the
cell's attention shape against a masked softmax, output and gradients
(``kernel_gradients``). One JSON line each.

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

# Queries whose bf16 scores against every key the wrong softmax holds at
# once.
QUERY_BLOCK = 256


def _configured(**changes):
    """The family's ``GPTConfig`` with ``changes``."""
    from chipbench.families import afmoe

    right = afmoe._model_config
    return _swapped(afmoe, "_model_config", lambda config, seq_len: (
        dataclasses.replace(right(config, seq_len), **changes)))


def _norms_left_out(*names):
    """``transformer._norm`` giving, under ``names``, a layer that holds
    the norm's weight and passes its input on."""
    import flax.linen as nn
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    class Passed(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones_init(), (x.shape[-1],),
                       jnp.float32)
            return x

    right = transformer._norm
    return _swapped(transformer, "_norm", lambda cfg, name: (
        Passed(name=name) if name in names else right(cfg, name)))


def _attend_with(change):
    """``transformer._attend`` with its window changed by
    ``change(cfg, window)``."""
    from horovod_tpu.models import transformer

    right = transformer._attend
    return _swapped(
        transformer, "_attend",
        lambda cfg, q, k, v, positions, core, window=0: right(
            cfg, q, k, v, positions, core, change(cfg, window)))


def _attend_with_a_bf16_softmax(cfg, q, k, v, positions, core, window=0):
    """``transformer._attend``'s arguments: the scores rounded to bf16 and
    the softmax computed in bf16, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    b, s, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    block = min(QUERY_BLOCK, s)

    @jax.checkpoint
    def queries(args):
        q, at = args                            # [b, block, h, d], [block]
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k,
                             preferred_element_type=jnp.float32)
                  * d ** -0.5).astype(jnp.bfloat16)
        keys = jnp.arange(s)[None, :]
        seen = keys <= at[:, None]
        if window:
            seen &= at[:, None] - keys < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    out = jax.lax.map(queries, (
        jnp.moveaxis(q.reshape(b, s // block, block, h, -1), 1, 0),
        jnp.arange(s).reshape(-1, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, -1)


def _attention_with_sigmoid(instead):
    """``Attention.__call__`` traced with ``instead`` where it calls
    ``jax.nn.sigmoid``, which it does for its gate alone."""
    import jax

    from horovod_tpu.models import transformer

    right = transformer.Attention.__call__

    def call(self, x, positions):
        with _swapped(jax.nn, "sigmoid", instead):
            return right(self, x, positions)

    return _swapped(transformer.Attention, "__call__", call)


@contextlib.contextmanager
def _second_norm_after_the_sum():
    """``MixerBlock`` returning ``post_norm(x + mixer(norm(x)))``: its
    second norm passes its input on, and the norm is made again from the
    layer's own weight on what the block returns."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    right = transformer.MixerBlock.__call__

    def call(self, x, positions):
        y, aux = right(self, x, positions)
        scale = self.variables["params"]["post_norm"]["scale"]
        y32 = y.astype(jnp.float32)
        var = jnp.mean(y32 * y32, axis=-1, keepdims=True)
        return (y32 * jax.lax.rsqrt(var + self.cfg.norm_eps) * scale
                ).astype(y.dtype), aux

    with _norms_left_out("post_norm"), _swapped(
            transformer.MixerBlock, "__call__", call):
        yield


def _without_the_shared_expert():
    """``MoEMlp`` less its shared expert's term, made again from the
    layer's own weights and taken off its output."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import moe

    right = moe.MoEMlp.__call__

    def call(self, x):
        out, aux = right(self, x)
        p = {name: w.astype(self.dtype)
             for name, w in self.variables["params"].items()
             if name.startswith("shared_")}
        h = x.astype(self.dtype)
        shared = jnp.dot(jax.nn.silu(jnp.dot(h, p["shared_gate"]))
                         * jnp.dot(h, p["shared_up"]), p["shared_down"])
        return out - shared.astype(out.dtype), aux

    return _swapped(moe.MoEMlp, "__call__", call)


def wrong_programs():
    """``(label, context manager)`` of every wrong program."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import rotary

    return (
        ("a window of 2,047", _configured(attn_window=2047)),
        ("a window of 2,049", _configured(attn_window=2049)),
        ("no window", _attend_with(lambda cfg, window: 0)),
        ("a window in the full layers too",
         _attend_with(lambda cfg, window: cfg.attn_window)),
        ("the full layers turned", _configured(rotary=True)),
        # the full layers of this model turn nothing, so `rotary` (which
        # `Attention` loads where it calls it) is the windowed layers' alone
        ("the windowed layers not turned", _swapped(
            rotary, "rotary", lambda x, *args, **kwargs: x)),
        ("no gate", _attention_with_sigmoid(jnp.ones_like)),
        ("silu in the gate", _attention_with_sigmoid(jax.nn.silu)),
        ("no norm a head", _norms_left_out("q_norm", "k_norm")),
        ("the second norm left out", _norms_left_out("post_norm")),
        ("the second norm after the residual sum",
         _second_norm_after_the_sum()),
        ("the embedding unscaled", _configured(embed_scale=1.0)),
        ("the routed sum without 2.826",
         _route_with(lambda o: ({**o, "scale": 1.0}, None))),
        ("no shared expert", _without_the_shared_expert()),
        ("the softmax in bf16", _swapped(transformer, "_attend",
                                         _attend_with_a_bf16_softmax)),
    )


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
                        "against the reference's on two of --seeds and the "
                        "kernels alone against a masked softmax, nothing "
                        "else")
    p.add_argument("--mixers-only", action="store_true",
                   help="of the comparisons, the whole model's mixers at "
                        "the cell's length alone (not the probe's)")
    p.add_argument("--only", nargs="+", metavar="WORD",
                   help="of the wrong programs, those whose label holds one "
                        "of these")
    p.add_argument("--rehearse", action="store_true",
                   help="the family's tiny sizes on whatever is there: "
                        "control flow alone, no reading means anything")
    args = p.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("trinity_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import afmoe
    from chipbench.reference import afmoe as reference
    from chipbench.setup_sources import enable_compile_cache

    enable_compile_cache()
    config, cell, _ = harness.load_cell("trinitymini-s16384")
    if args.rehearse:
        config = {**config, **afmoe.REHEARSAL["config"]}
        cell = {**cell, **afmoe.REHEARSAL["traffic"]}

    def fresh(job, seed):
        """The whole model on a fresh initialisation and the cell's batch."""
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        params, extra = jax.jit(job.init)(k_init)
        return params, extra, jax.jit(lambda k: job.make_batch(k, 1))(k_batch)

    def readings(label, seed):
        """The family's own check, and its comparison of the whole model's
        mixers at the cell's length (which the cell reads on the window's
        parameters and batch, here on a fresh initialisation), their
        values parsed from their lines."""
        job = afmoe.build(config, cell)         # a fresh trace each time
        out = {"program": label, "seed": seed}
        checks = [] if args.mixers_only else job.check(jax.random.key(seed))
        for c in checks + job.mixers_close(*fresh(job, seed)):
            found = re.findall(
                r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+", str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[0]) if found else None}
        out["failed"] = [name for name, c in out.items()
                         if isinstance(c, dict) and not c["ok"]]
        print(json.dumps(out), flush=True)

    def chosen(label):
        return not args.only or any(word in label for word in args.only)

    for seed in () if args.wrong_only or args.loss_only else args.seeds:
        readings("as it is", seed)
    if args.sound_only:
        return
    # (a context manager of `wrong_programs` is entered once)
    wrong = lambda label: dict(wrong_programs())[label]
    for label, _ in () if args.loss_only else wrong_programs():
        # a lower precision on every seed, wrong mathematics on the first
        for seed in args.seeds if "bf16" in label else args.seeds[:1]:
            if chosen(label):
                with wrong(label):
                    jax.clear_caches()
                    readings(label, seed)
    jax.clear_caches()
    if args.wrong_only or args.mixers_only:
        return

    # the step-loss comparison's regime: the whole model, a fresh
    # initialisation, the cell's batch; the package as it is, the
    # reference itself at the TPU's default precision, and the programs a
    # loss might be hoped to tell (on the first seed)
    told = ("no window", "a window of 2,047", "the softmax in bf16")
    for seed in args.seeds[:2]:
        params, extra, batch = fresh(afmoe.build(config, cell), seed)
        want, _ = reference.loss(params, extra["buffers"], batch, config)
        with _swapped(jax, "default_matmul_precision",
                      lambda name: contextlib.nullcontext()):
            coarse, _ = reference.loss(params, extra["buffers"], batch,
                                       config)
        rel = lambda value: abs(value - want) / max(abs(want), 1.0)
        out = {"program": "whole model, fresh initialisation", "seed": seed,
               "reference": want, "reference_at_default_precision": coarse,
               "its_rel": rel(coarse), "bound": afmoe.LOSS_REL_BOUND}
        for label in ("as it is",) + told * (seed == args.seeds[0]):
            with contextlib.nullcontext() if label == "as it is" else wrong(
                    label):
                jax.clear_caches()
                got = float(jax.jit(afmoe.build(config, cell).loss)(
                    params, extra, batch)[0])
            out[label] = {"loss": got, "rel": rel(got)}
        print(json.dumps(out), flush=True)
        del params
    jax.clear_caches()
    kernel_gradients(args.seeds[0], cell["seq_len"], config)


def kernel_gradients(seed, s, config):
    """The flash kernels alone at the cell's attention shape (one sequence
    of ``s``, the configuration's heads), with the window and without: the
    output and the gradients of q, k and v under a random cotangent
    against one masked softmax over whole rows in float32 by query blocks,
    on the same bf16 operands. The probe holds the backward kernel inside
    a model at 4,096 positions; this reads it at the length the step runs,
    band steps and all. One JSON line a call."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference.afmoe import seen
    from horovod_tpu.ops.flash_attention import flash_attention

    h, h_kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, block = config["head_dim"], min(QUERY_BLOCK, s)
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v, do = (jax.random.normal(key, (1, s, heads, d), jnp.bfloat16)
                   for key, heads in zip(keys, (h, h_kv, h_kv, h)))

    def softmax_rows(q, k, v, window):
        k, v = (jnp.repeat(t[0], h // h_kv, axis=1) for t in (k, v))

        @jax.checkpoint
        def queries(args):
            q, at = args                        # [block, h, d], [block]
            scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(
                seen(at, s, window)[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        return jax.lax.map(queries, (
            q[0].reshape(-1, block, h, d),
            jnp.arange(s).reshape(-1, block))).reshape(1, s, h, d)

    f32 = lambda *ts: [t.astype(jnp.float32) for t in ts]
    for window in (config["sliding_window"], None):
        kernels = lambda q, k, v: flash_attention(
            q, k, v, causal=True, scale=d ** -0.5,
            **({} if window is None else {"window": window}))
        o, back = jax.vjp(kernels, q, k, v)
        got = (o, *back(do))
        with jax.default_matmul_precision("highest"):
            o, back = jax.vjp(
                lambda q, k, v: softmax_rows(q, k, v, window), *f32(q, k, v))
            want = (o, *back(*f32(do)))
        print(json.dumps({
            "program": "the flash kernels against a masked softmax",
            "shape": [1, s, h, h_kv, d], "window": window, "seed": seed,
            "rel_l2": {name: float(
                jnp.linalg.norm(g.astype(jnp.float32) - w)
                / jnp.linalg.norm(w))
                for name, g, w in zip(("o", "dq", "dk", "dv"), got, want)}}),
            flush=True)


if __name__ == "__main__":
    main()
