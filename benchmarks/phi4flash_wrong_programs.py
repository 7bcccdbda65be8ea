#!/usr/bin/env python
"""phi4flash_wrong_programs.py — what the comparisons of the cell
``phi4flash-s16384`` read for the program as it is, for a lower precision
and for wrong mathematics, on the chip.

    chiprun -- python benchmarks/phi4flash_wrong_programs.py

It runs ``chipbench/families/phi4flash.py``'s own comparisons (``check`` on
the probe ``WA*UX-`` at the published widths, 2,048 positions: gradients
leaf by leaf in the cell's dtype, and loss and gradients with float32
products at the highest precision; ``layers_close`` on the whole stage at
the cell's 16,384 positions, which the cell reads on the window's
parameters and this script on a fresh initialisation: every mixer against
the float32 reference on its own input, a unit on the memory the last
Mamba-1 layer sowed and a cross layer on the keys and values the reference
makes of the full layer's input) first for the package as it is over
``--seeds`` (the margins the bounds were set from), then with a program
wrong in one thing on the same seeds: the scan's decays and state in bf16;
no window in layer 15; ``lambda`` 0; ``lambda_init`` at layer number 1 and
not the layer's own; the pair's norm left out; the unit reading the gated
output and not ``m``; the cross layer reading its own input's keys and not
layer 17's; a LayerNorm without its mean. The parameter tree stays the
package's in every one, so the reference reads what it always reads. Each
must fail at least one bound (``"failed"`` in its line). One JSON line
each.

``--layers-only`` skips the probe, ``--probe-only`` the whole stage;
``--rehearse`` walks the control flow on the CPU at the family's tiny
sizes.

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


def _decays_in_bf16():
    import jax.numpy as jnp

    from horovod_tpu.models import mamba

    return _swapped(mamba, "DECAY_DTYPE", jnp.bfloat16)


def _no_window():
    """The windowed kind built as the full one is (no window handed to the
    kernels or the mask)."""
    import dataclasses

    from horovod_tpu.models import transformer

    full = transformer.KINDS["*"].build
    return _swapped(transformer, "KINDS", {
        **transformer.KINDS, "W": dataclasses.replace(
            transformer.KINDS["W"], build=lambda cfg, depth=0: full(
                cfg, depth=depth))})


def _lambda_init_of_layer_1():
    from horovod_tpu.models import transformer

    right = transformer.differential_lambda_init
    return _swapped(transformer, "differential_lambda_init",
                    lambda depth: right(1))


@contextlib.contextmanager
def _lambda_zero():
    """``lambda`` 0: the second map dropped from the difference (the scale
    ``1 - lambda_init`` stays)."""
    import jax.numpy as jnp

    right = jnp.split

    def wrong(ary, parts, axis=0):
        out = right(ary, parts, axis)
        # the one split of a float32 [.., heads, 2 e] array in two by heads
        if (parts == 2 and axis == -2 and ary.dtype == jnp.float32
                and ary.ndim == 4):
            return [out[0], jnp.zeros_like(out[1])]
        return out

    with _swapped(jnp, "split", wrong):
        yield


@contextlib.contextmanager
def _no_pair_norm():
    """``rsqrt`` 1 inside ``Attention`` alone (the LayerNorms keep
    theirs)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    right = transformer.Attention.__call__

    def wrong(self, *args, **kwargs):
        with _swapped(jax.lax, "rsqrt", lambda t: jnp.ones_like(t)):
            return right(self, *args, **kwargs)

    with _swapped(transformer.Attention, "__call__", wrong):
        yield


def _unit_reads_the_gated_output():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import mamba

    right = mamba.Mamba1Mixer.__call__

    def wrong(self, x):
        out, memory = right(self, x)
        z = jnp.split(jnp.dot(x.astype(self.dtype), self.variables["params"][
            "in_proj"].astype(self.dtype)), 2, -1)[1]
        return out, (memory.astype(jnp.float32) * jax.nn.silu(
            z.astype(jnp.float32))).astype(memory.dtype)

    return _swapped(mamba.Mamba1Mixer, "__call__", wrong)


@contextlib.contextmanager
def _cross_reads_its_own_keys():
    """The cross layer's keys and values made **of the cross layer's own
    input** and not handed from layer 17: its query projection's first key
    heads stand for a key and a value projection, so the tree is the
    package's."""
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    right = transformer.Attention.__call__

    def wrong(self, x, positions, read=None):
        if self.shared and self.has_variable("params", "q"):
            kernel = self.variables["params"]["q"]["kernel"]
            own = jnp.einsum("...d,dhe->...he", x.astype(self.cfg.dtype),
                             kernel[:, :read[0].shape[-2]].astype(
                                 self.cfg.dtype))
            read = (own, own)
        return right(self, x, positions, read)

    with _swapped(transformer.Attention, "__call__", wrong):
        yield


def _layer_norm_without_its_mean():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer

    class NoMean(transformer.LayerNorm):
        @transformer.nn.compact
        def __call__(self, x):
            scale = self.param("scale", transformer.nn.initializers.ones_init(),
                               (x.shape[-1],), jnp.float32)
            bias = self.param("bias", transformer.nn.initializers.zeros_init(),
                              (x.shape[-1],), jnp.float32)
            x32 = x.astype(jnp.float32)
            var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            return (x32 * jax.lax.rsqrt(var + self.eps) * scale
                    + bias).astype(x.dtype)

    return _swapped(transformer, "LayerNorm", NoMean)


def wrong_programs():
    """``(label, context manager)`` of every wrong program."""
    return (
        ("the decays and the state in bf16", _decays_in_bf16()),
        ("no window in layer 15", _no_window()),
        ("lambda 0", _lambda_zero()),
        ("lambda_init at layer number 1", _lambda_init_of_layer_1()),
        ("the pair's norm left out", _no_pair_norm()),
        ("the unit reading the gated output",
         _unit_reads_the_gated_output()),
        ("the cross layer reading its own input's keys",
         _cross_reads_its_own_keys()),
        ("a LayerNorm without its mean", _layer_norm_without_its_mean()),
    )


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147600601, 2147600602])
    p.add_argument("--sound-only", action="store_true",
                   help="the package as it is over --seeds, nothing else")
    p.add_argument("--wrong-only", action="store_true",
                   help="the wrong programs over --seeds, nothing else")
    p.add_argument("--layers-only", action="store_true",
                   help="of the comparisons, the whole stage's layers at "
                        "the cell's length alone (not the probe's)")
    p.add_argument("--probe-only", action="store_true",
                   help="of the comparisons, the probe's alone")
    p.add_argument("--only", nargs="+", metavar="WORD",
                   help="of the wrong programs, those whose label holds one "
                        "of these")
    p.add_argument("--rehearse", action="store_true",
                   help="the family's tiny sizes on whatever is there: "
                        "control flow alone, no reading means anything")
    args = p.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit("phi4flash_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import phi4flash
    from chipbench.setup_sources import enable_compile_cache

    enable_compile_cache()
    config, cell, _ = harness.load_cell("phi4flash-s16384")
    if args.rehearse:
        config = {**config, **phi4flash.REHEARSAL["config"]}
        cell = {**cell, **phi4flash.REHEARSAL["traffic"]}

    def readings(label, seed):
        """The family's own check and its comparison of the whole stage's
        layers at the cell's length on a fresh initialisation, their values
        parsed from their lines."""
        job = phi4flash.build(config, cell)     # a fresh trace each time
        out = {"program": label, "seed": seed}
        checks = [] if args.layers_only else job.check(jax.random.key(seed))
        if not args.probe_only:
            k_init, k_batch = jax.random.split(jax.random.key(seed))
            params, extra = jax.jit(job.init)(k_init)
            batch = jax.jit(lambda k: job.make_batch(k, 1))(k_batch)
            checks += job.layers_close(params, extra, batch)
            del params, batch
        for c in checks:
            found = re.findall(
                r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+", str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[-1]) if found
                           else None}
        out["failed"] = [name for name, c in out.items()
                         if isinstance(c, dict) and not c["ok"]]
        print(json.dumps(out), flush=True)

    for seed in () if args.wrong_only else args.seeds:
        readings("as it is", seed)
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
                readings(label, seed)
    jax.clear_caches()


if __name__ == "__main__":
    main()
