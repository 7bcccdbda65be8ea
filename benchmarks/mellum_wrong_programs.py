#!/usr/bin/env python
"""mellum_wrong_programs.py — what the comparisons of the cell
``mellum2-s16384`` read for the program as it is, for a lower precision and
for wrong mathematics, on the chip.

    chiprun -- python benchmarks/mellum_wrong_programs.py

It runs ``chipbench/families/mellum.py``'s own comparisons (``check`` on
the probe ``WE*E`` at the published widths and shares, 4,096 positions:
gradients leaf by leaf given the program's experts, the router against a
float32 one on its own input, the two choices of experts; and
``layers_close`` on the whole model at 16,384 positions, which the cell
reads on the window's parameters and this script on a fresh
initialisation: the first and the last windowed mixer and the full one
against the float32 reference by query blocks, as the step runs them and
built again with float32 products, and the last expert layer given the
program's experts) first for the package as it is over ``--seeds`` (the
margins the bounds were set from), then with a program wrong in one thing
over the same seeds: the plain rotary in the full layer; the YaRN
frequencies without the factor on ``cos`` and ``sin``; ``high`` and ``low`` not truncated; a window of 2,048;
weights not renormalised; the softmax of the scores in bf16; the router's
product at the TPU's default precision. The parameter tree stays the
package's in every one, so the reference reads what it always reads. Each
must fail at least one bound (``"failed"`` in its line). One JSON line
each.

``--layers-only`` skips the probe (a minute a program in place of three);
``--rehearse`` walks the control flow on the CPU at the family's tiny
sizes.

A builder's script: it decides nothing. It refuses to run without a TPU.
"""

import argparse
import dataclasses
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.qwen3next_wrong_programs import _route_with, _swapped
from benchmarks.trinity_wrong_programs import _attend_with_a_bf16_softmax


def _configured(change):
    """The family's ``GPTConfig`` changed by ``change(cfg) -> fields``."""
    from chipbench.families import mellum

    right = mellum._model_config

    def wrong(config, seq_len):
        cfg = right(config, seq_len)
        return dataclasses.replace(cfg, **change(cfg))

    return _swapped(mellum, "_model_config", wrong)


def wrong_programs():
    """``(label, context manager)`` of every wrong program."""
    from horovod_tpu.models import transformer

    scaled = lambda **fields: _configured(lambda cfg: {
        "rotary_scaling": cfg.rotary_scaling._replace(**fields)})
    return (
        ("the plain rotary in the full layer",
         _configured(lambda cfg: {"rotary_scaling": None})),
        ("the YaRN frequencies without the factor on cos and sin",
         scaled(attention_factor=1.0)),
        ("high and low not truncated", scaled(truncate=False)),
        ("a window of 2,048", _configured(lambda cfg: {"attn_window": 2048})),
        ("weights not renormalised",
         _route_with(lambda o: ({**o, "renormalise": False}, None))),
        ("the softmax in bf16", _swapped(transformer, "_attend",
                                         _attend_with_a_bf16_softmax)),
        ("the router at the default precision in bf16",
         _route_with(lambda o: (o, lambda real, a, b: real(a, b)))),
    )


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", type=int, nargs="+",
                   default=[2147500301, 2147500302])
    p.add_argument("--sound-only", action="store_true",
                   help="the package as it is over --seeds, nothing else")
    p.add_argument("--wrong-only", action="store_true",
                   help="the wrong programs over --seeds, nothing else")
    p.add_argument("--layers-only", action="store_true",
                   help="of the comparisons, the whole model's layers at "
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
        sys.exit("mellum_wrong_programs: no TPU, nothing to measure")

    from chipbench import run as harness
    from chipbench.families import mellum
    from chipbench.setup_sources import enable_compile_cache

    enable_compile_cache()
    config, cell, _ = harness.load_cell("mellum2-s16384")
    if args.rehearse:
        config = {**config, **mellum.REHEARSAL["config"]}
        cell = {**cell, **mellum.REHEARSAL["traffic"]}

    def fresh(job, seed):
        """The whole model on a fresh initialisation and the cell's batch."""
        k_init, k_batch = jax.random.split(jax.random.key(seed))
        params, extra = jax.jit(job.init)(k_init)
        return params, extra, jax.jit(lambda k: job.make_batch(k, 1))(k_batch)

    def readings(label, seed):
        """The family's own check, and its comparison of the whole model's
        layers at the cell's length (which the cell reads on the window's
        parameters and batch, here on a fresh initialisation), their
        values parsed from their lines."""
        job = mellum.build(config, cell)        # a fresh trace each time
        out = {"program": label, "seed": seed}
        checks = [] if args.layers_only else job.check(jax.random.key(seed))
        for c in checks + job.layers_close(*fresh(job, seed)):
            found = re.findall(
                r"[-+]?\d+\.\d+(?:e[-+]?\d+)?|\d+\.?\d*e[-+]\d+", str(c.value))
            out[c.name] = {"ok": c.ok, "value": str(c.value)[:160],
                           "first_number": float(found[0]) if found else None}
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
            with dict(wrong_programs())[label]:
                jax.clear_caches()
                readings(label, seed)
    jax.clear_caches()


if __name__ == "__main__":
    main()
