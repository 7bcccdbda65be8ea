#!/usr/bin/env python
"""flash_kernels.py — the flash-attention kernels alone, on the chip.

    chiprun -- python benchmarks/flash_kernels.py \
        --shape 2,4096,20,20,64 --blocks derived,128x128,bwd=512x512
    chiprun -- python benchmarks/flash_kernels.py --shape 2,8192,32,32,192,128
    chiprun -- python benchmarks/flash_kernels.py \
        --shape 2,8192,32,32,192,128 --rotated 64

For every score tile asked for it jits one forward + ``vjp`` of
``flash_attention`` at ``--shape`` (batch, seq, heads, kv_heads, head_dim
and, since PR 48, optionally the values' width where it is not the keys':
``2,8192,32,32,192,128`` is ``kanana2-s8192``'s latent attention, and
``..,256,128`` and ``..,256`` beside it price the zeros a padded width
would multiply; bf16, causal unless ``--no-causal``), runs it ``--iters`` times inside one
profiler trace and prints one JSON line: the device milliseconds a call of
``hvt_flash_fwd`` and ``hvt_flash_bwd`` (median over the iterations, read
from the trace by the kernels' names; ``hvt_flash_dq`` and
``hvt_flash_dkv`` where ``--source`` is a copy from before PR 29, which
had those two in the backward pass), the wall-clock milliseconds of the
whole call, and how far its results are from the first tile's (relative
L2). A tile is ``derived`` (each kernel's own rule), ``QxK`` (passed as
``block_q``, ``block_k``, so to every kernel) or ``fwd=QxK`` / ``bwd=QxK``
(that kernel's derived tile replaced, the other's kept). ``--einsum`` adds
the einsum attention of ``models/transformer.py`` at the same shape, wall
clock only, and alone: inside a training step XLA schedules it otherwise
(PERF.md section 6, PR 27: 7.82 ms here, 4.70 in the step), so it does not
say where ``"auto"``'s crossover belongs; the cells do. ``--source FILE`` times another copy of
``ops/flash_attention.py`` (the parent commit's) instead. ``--rotated E``
(since PR 49) takes the last ``E`` of the query-key width as a rotated pair,
``q_r`` a head and **one ``k_r`` a position**, and times every tile twice on
the same numbers: ``"entry": "whole"`` assembles ``q_n | q_r`` and ``k_n |
k_r`` (``k_r`` broadcast over the heads) for the one-width entry, as latent
attention did until PR 49, and ``"entry": "parts"`` hands the kernels the
four parts (``flash_attention(q_n, k_n, v, q_r=.., k_r=..)``); both give
``(o, dq_n, dq_r, dk_n, dk_r, dv)``, so ``rel_l2_vs_first`` compares the
second with the first. A ``--source`` without the two-part entry runs
``whole`` alone. ``--against FILE`` (since PR 50) times FILE's kernels
first, in the same process, and ``rel_l2_vs_first`` of every line is then
against FILE's first tile: ``--against _parent/horovod_tpu/ops/
flash_attention.py`` puts the parent beside the change, and 0.0 says the
change gives ``o`` and every gradient to the last bit. ``--shape`` takes
several shapes, separated by ``+``, and ``/E`` after one is its own
``--rotated``. Since PR 54 every line also says the same result by
result (``rel_l2_by_result``: ``o``, ``dq``, ``dk``, ``dv``, or with a
rotated pair ``o``, ``dq_n``, ``dq_r``, ``dk_n``, ``dk_r``, ``dv``), so a
change of the backward kernel alone shows which gradient moved;
``--source`` takes several files joined by ``,`` (variants of one kernel
beside the parent in one process); and ``/choice`` after a shape hands
the kernels a choice of keys (``keyevl2-s16384``'s masked calls: int8
``[b, s, s]``, the 1,024 keys before a query and every sixteenth of the
causal rest, made on the device; the kernels walk every causal tile
whatever the mask holds): ``--shape 1,16384,32,4,128/choice``; and
``/window=N`` a window of ``N`` keys (``trinitymini-s16384``'s windowed
calls beside its full ones: ``--shape
1,16384,32,4,128+1,16384,32,4,128/window=2048``; an einsum path has no
window here).

A microbenchmark, not the yardstick: the cell that decides is
``gpt2l-s4096`` of ``BENCHMARK.json``. It refuses to run without a TPU.
"""

import argparse
import glob
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time

KERNELS = ("hvt_flash_fwd", "hvt_flash_bwd", "hvt_flash_dq", "hvt_flash_dkv")


def load_flash(source):
    if source is None:
        from horovod_tpu.ops import flash_attention
        return flash_attention
    spec = importlib.util.spec_from_file_location("flash_under_test", source)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_inputs(shape, rotated=0):
    """``(q, k, v), do``, or with a rotated width ``(q_n, q_r, k_n, k_r,
    v), do``: the same numbers, ``k_r`` the first head's."""
    import jax.numpy as jnp
    import numpy as np

    b, s, h, h_kv, d, d_v = (*shape, shape[-1])[:6]
    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.normal(size=(b, s, *dims)), jnp.bfloat16)
                   for dims in ((h, d), (h_kv, d), (h_kv, d_v), (h, d_v)))
    if not rotated:
        return (q, k, v), do
    n = d - rotated
    return (q[..., :n], q[..., n:], k[..., :n], k[:, :, 0, n:], v), do


def einsum_attention(q, k, v, causal):
    """The attention ``models/transformer.py`` runs under the crossover."""
    import jax
    import jax.numpy as jnp

    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / q.shape[-1] ** 0.5
    if causal:
        pos = jnp.arange(q.shape[1])
        scores = jnp.where(pos[None, :] <= pos[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def make_choice(b, s):
    """A choice of keys ``[b, s, s]`` int8 for the masked calls: the 1,024
    keys before a query and a sixteenth of the causal rest, so every row
    sees a key and no causal tile is empty, as no tile of an indexer's
    choice is."""
    import jax
    import jax.numpy as jnp

    def mask():
        t = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        u = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        some = (u * 7 + t * 13) % 16 == 0
        keep = (u <= t) & ((t - u < 1024) | some)
        return jnp.broadcast_to(keep.astype(jnp.int8), (b, s, s))

    return jax.jit(mask)()


RESULTS = {4: ("o", "dq", "dk", "dv"),
           6: ("o", "dq_n", "dq_r", "dk_n", "dk_r", "dv")}


def whole_width(attend):
    """``attend(q, k, v)`` as a function of the four parts: the query and
    the key assembled, the one rotated key a position broadcast over the
    heads (``models/mla.py``'s ``whole_key``)."""
    import jax.numpy as jnp

    from horovod_tpu.models.mla import whole_key

    def of_parts(q_n, q_r, k_n, k_r, v, **choice):
        return attend(jnp.concatenate([q_n, q_r], -1), whole_key(k_n, k_r),
                      v, **choice)

    return of_parts


def call_and_vjp(attend):
    import jax

    def run(operands, do, chosen=None):
        # a choice is an operand of the jitted call and no constant of it
        choice = {} if chosen is None else {"choice": chosen}
        o, vjp = jax.vjp(lambda *parts: attend(*parts, **choice), *operands)
        return (o, *vjp(do))

    return jax.jit(run)


def kernel_durations(trace_dir):
    """{kernel: [ms, ...]} of chip 0's events, in the order they ran."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    out = {name: [] for name in KERNELS}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in sorted(line.events, key=lambda e: e.start_ns):
                for name in KERNELS:
                    # %hvt_flash_fwd.3, %transpose_jvp_hvt_flash_bwd__.1
                    if name in e.name.split(" = ")[0]:
                        out[name].append(e.duration_ns / 1e6)
    return out


def measure(flash, shape, tiles, causal=True, iters=5, einsum=False,
            rotated=0, first=None, choice=False, window=None):
    """One dict a tile (with a rotated width two, one an entry; and one
    for the einsum path), as the module docstring describes, and what
    they were compared with: ``first``, or the first tile's results."""
    import inspect

    import jax
    from chip_smoke import rel_l2

    args = make_inputs(shape, rotated)
    if choice:
        args += (make_choice(shape[0], shape[1]),)
    derive = flash._derive_tile
    runs = []
    for tile in tiles:
        blocks = (dict(zip(("block_q", "block_k"), tile))
                  if isinstance(tile, tuple) else {})
        banded = {} if window is None else {"window": window}
        whole = lambda q, k, v, blocks=blocks, **chosen: (
            flash.flash_attention(q, k, v, causal=causal, **banded, **blocks,
                                  **chosen))
        if not rotated:
            runs.append((tile, None, call_and_vjp(whole)))
            continue
        runs.append((tile, "whole", call_and_vjp(whole_width(whole))))
        if "q_r" in inspect.signature(flash.flash_attention).parameters:
            runs.append((tile, "parts", call_and_vjp(
                lambda q_n, q_r, k_n, k_r, v, blocks=blocks, **chosen:
                flash.flash_attention(q_n, k_n, v, q_r=q_r, k_r=k_r,
                                      causal=causal, **blocks, **chosen))))
    if einsum:
        plain = lambda q, k, v, **none: einsum_attention(q, k, v, causal)
        runs.append(("einsum", None, call_and_vjp(
            whole_width(plain) if rotated else plain)))
    lines = []
    for tile, entry, fn in runs:              # compile, warm up, compare
        line = {"tile": tile, "shape": list(shape), "causal": causal}
        if entry:
            line.update(entry=entry, rotated=rotated)
        if choice:
            line["choice"] = True
        if window is not None:
            line["window"] = window
        lines.append(line)
        if isinstance(tile, str) and "=" in tile:
            # traced here, once: the rule is read outside the jitted call
            kernel, forced = tile.split("=")
            flash._derive_tile = lambda k, *a, kernel=kernel, forced=forced: (
                parse_tile(forced) if k == kernel else derive(k, *a))
        try:
            got = jax.device_get(fn(*args))
        except Exception as e:      # a tile the compiler refuses: say so
            line["refused"] = str(e).split("\n")[0][-300:]
            continue
        finally:
            flash._derive_tile = derive
        first = got if first is None else first
        line["rel_l2_vs_first"] = rel_l2(got, first)
        line["rel_l2_by_result"] = {
            name: rel_l2(mine, theirs)
            for name, mine, theirs in zip(RESULTS[len(got)], got, first)}
    ran = [(line, fn) for line, (*_, fn) in zip(lines, runs)
           if "refused" not in line]
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for line, fn in ran:
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            line["wall_ms"] = (time.perf_counter() - t0) * 1e3 / iters
        jax.profiler.stop_trace()
        spans = kernel_durations(trace_dir)
    timed = [line for line, _ in ran if line["tile"] != "einsum"]
    for name, ms in spans.items():
        if not ms:                  # a kernel this source has not got
            continue
        if len(ms) != len(timed) * iters:
            raise SystemExit(f"{name}: {len(ms)} events in the trace, "
                             f"expected {len(timed)} x {iters}")
        for i, line in enumerate(timed):
            line[name.removeprefix("hvt_flash_") + "_ms"] = (
                statistics.median(ms[i * iters:(i + 1) * iters]))
    return lines, first


def parse_tile(text):
    return text if text == "derived" or "=" in text else tuple(
        int(x) for x in text.split("x"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="2,4096,20,20,64")
    ap.add_argument("--blocks", default="derived")
    ap.add_argument("--no-causal", action="store_true")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--einsum", action="store_true")
    ap.add_argument("--source")
    ap.add_argument("--against")
    ap.add_argument("--rotated", type=int, default=0, metavar="E")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("flash_kernels.py times kernels on a TPU; found "
                         f"{jax.default_backend()}")
    tiles = [parse_tile(t) for t in a.blocks.split(",")]
    sources = ([a.against] if a.against else []) + (
        a.source.split(",") if a.source else [None])
    for shape in a.shape.split("+"):
        shape, _, own = shape.partition("/")
        choice = own == "choice"
        window = int(own[7:]) if own.startswith("window=") else None
        rotated = a.rotated if choice or window or not own else int(own)
        first = None
        for source in sources:
            flash = load_flash(source)
            lines, first = measure(
                flash, tuple(int(x) for x in shape.split(",")), tiles,
                causal=not a.no_causal, iters=a.iters, einsum=a.einsum,
                rotated=rotated, first=first, choice=choice, window=window)
            for line in lines:
                line["source"] = os.path.relpath(flash.__file__)
                line["device"] = jax.devices()[0].device_kind
                print(json.dumps(line), flush=True)
            jax.clear_caches()      # the next source's kernels are traced


if __name__ == "__main__":
    main()
