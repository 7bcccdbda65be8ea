#!/usr/bin/env python
"""step_text.py — the training step of a benchmark cell as text, hashed
without the places it was written from: what a change that should move no
number shows before any number is read.

    chiprun -- python benchmarks/step_text.py --cells all
    chiprun --chips 4 -- python benchmarks/step_text.py --cells gpt2l-dp4
    (cd _parent && python ../benchmarks/step_text.py --cells all)

For each cell: the job and the step ``chipbench.run`` builds (the cell's
family and spelling, published sizes), lowered from shapes with the
spelling's shardings and nothing initialised; then sha256 of the lowered
text and, with ``--compile``, of the optimised program's
(``compiled.as_text()``). A text is hashed once its source locations are
gone: the ``stack_frame_id`` of an operation's metadata with the tables
of files, functions and lines it points into, and every Mosaic kernel's
body, which a custom call carries as MLIR bytecode
with file and line inside, printed again without them. Scopes, kernel
names and jitted functions' names stay: they are what the benchmark's
regions and per-layer metrics read. Run from another checkout (the
parent's, ``_parent/``) it reads that checkout's ``horovod_tpu`` and
``chipbench``. One JSON line a cell, and
``chiprun_out/step_text_<checkout>.json``.

``--describe v5e:2x2`` lowers (and compiles) for a chip that is described
and not attached (with ``--compile`` also ``step_bytes``, the cell's
``hbm_step`` to the byte: ``keyevl2-s16384``'s 11.5908 GiB at PR 64),
with the backend's name steered to ``tpu`` from here so that every rule
takes its TPU branch: a rehearsal, not the chip's word.

A builder's script: it decides nothing.
"""

import argparse
import base64
import hashlib
import importlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.getcwd())

# a quote is ``"``, ``\"`` or ``\22`` by the text's kind
_BODY = re.compile(r'(body(?:\\22|\\?"): ?(?:\\22|\\?"))([A-Za-z0-9+/=]+)')
# an operation's place (a frame of the tables at a module's head), and
# the tables: files, functions, lines
_PLACE = re.compile(r' ?stack_frame_id=\d+|^(?:FileNames|FunctionNames|'
                    r'FileLocations|StackFrames)\n(?:\d+ .*\n)*', re.M)


def _body_without_places(match):
    from jax._src.lib.mlir import ir

    with ir.Context() as context:
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(2)))
        asm = module.operation.get_asm(enable_debug_info=False)
    return match.group(1) + hashlib.sha256(asm.encode()).hexdigest()


def digest(text):
    """``(sha256, kernel bodies)`` of a lowered or compiled program's text
    with its source locations taken out."""
    text, bodies = _BODY.subn(_body_without_places, text)
    return hashlib.sha256(_PLACE.sub("", text).encode()).hexdigest(), bodies


def step_of(name, devices):
    """The cell's step lowered as ``chipbench.run`` lowers it, from
    shapes."""
    import jax
    from chipbench import run

    config, cell, _ = run.load_cell(name)
    chips = cell["chips"]
    if len(devices) < chips:
        raise SystemExit(f"{name} asks for {chips} chips, {len(devices)} here")
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    job = family.build(config, cell)
    spelled = importlib.import_module(
        f"chipbench.spellings.{cell['spelling']}").build(
            job, list(devices[:chips]))
    placed = lambda tree, sharding: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                       weak_type=a.weak_type), tree)
    key = jax.random.key(0)
    params, extra = placed(jax.eval_shape(job.init, key),
                           spelled.state_sharding)
    opt_state = placed(jax.eval_shape(spelled.tx.init, params),
                       spelled.state_sharding)
    batch = placed(jax.eval_shape(lambda k: job.make_batch(k, chips), key),
                   spelled.batch_sharding)
    return jax.jit(spelled.step, donate_argnums=(0, 1, 2)).lower(
        params, extra, opt_state, batch)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="all",
                    help="names of cells, or 'all' for every cell the "
                         "devices here are enough for")
    ap.add_argument("--compile", action="store_true",
                    help="also the optimised program's text")
    ap.add_argument("--describe", default=None, metavar="TOPOLOGY")
    a = ap.parse_args(argv)

    import jax

    if a.describe:
        from jax.experimental import topologies

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=a.describe).devices
        jax.default_backend = lambda: "tpu"
        jax.devices = lambda *_: list(devices)
    elif jax.default_backend() != "tpu":
        raise SystemExit("step_text.py reads the chip's program: no TPU "
                         f"here ({jax.default_backend()}); --describe "
                         "v5e:2x2 rehearses")
    import horovod_tpu as hvt
    from chipbench import run

    hvt.init()
    devices = jax.devices()
    cells = [w["name"] for w in run.read_json("BENCHMARK.json")["workloads"]
             if w["chips"] <= len(devices)] if a.cells == "all" \
        else a.cells.split(",")
    out = {}
    for name in cells:
        t0 = time.perf_counter()
        lowered = step_of(name, devices)
        here = out[name] = dict(zip(("lowered", "kernel_bodies"),
                                    digest(lowered.as_text())))
        here["lower_s"] = round(time.perf_counter() - t0, 1)
        if a.compile:
            t0 = time.perf_counter()
            compiled = lowered.compile()
            here["compiled"], _ = digest(compiled.as_text())
            analysis = compiled.memory_analysis()
            here["temp_bytes"] = analysis.temp_size_in_bytes
            # what ``chipbench.run`` reports as ``hbm_step``
            here["step_bytes"] = (
                analysis.argument_size_in_bytes
                + analysis.output_size_in_bytes + analysis.temp_size_in_bytes
                - analysis.alias_size_in_bytes)
            here["compile_s"] = round(time.perf_counter() - t0, 1)
        print(json.dumps({"cell": name, **here}), flush=True)
    tree = os.path.basename(os.getcwd())
    target = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chiprun_out")
    os.makedirs(target, exist_ok=True)
    with open(os.path.join(target, f"step_text_{tree}.json"), "w") as f:
        json.dump({"described": a.describe, "cells": out}, f, indent=1)


if __name__ == "__main__":
    main()
