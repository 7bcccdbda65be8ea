"""From the names a compiled step carries to a split of its time:
forward, recompute, backward, reduce, update, and what nothing names.

Where the names are. JAX gives every operation of a program a name
stack (``metadata.op_name``): ``jit(step)/jvp(GPT)/block_0/mlp/up/
dot_general`` in the forward pass, ``jit(step)/transpose(jvp(GPT))/...``
in the backward pass, ``.../checkpoint/rematted_computation/...`` where
``remat`` runs a block again; the package adds ``hvt_reduce_gradients``
and ``hvt_optimizer_update`` (``horovod_tpu.jax.REDUCE_SCOPE``,
``UPDATE_SCOPE``) and ``horovod_tpu.models.GPT`` adds ``embed`` and
``lm_head``. A TPU trace holds the whole program it ran: plane
``/host:metadata`` has one event metadata a program, whose one stat
``Hlo Proto`` is a serialized ``xla.HloProto``. That is read here, and
not the ``tf_op`` stat of an ``XLA Ops`` event's metadata, for a
reason found on libtpu 0.0.34 (my chip run, PR 24): ``tf_op`` is the
name of a fusion's *root* alone, and XLA fuses AdamW's arithmetic into
the ``add`` of ``optax.apply_updates``, which the benchmark's step calls
outside the package, so the optimizer's fusions read bare
``jit(step)/add``; the program says what a fusion holds.

Nothing but the standard library parses it: the only ``xplane_pb2`` and
``hlo_pb2`` installed are tensorflow's, which does not belong in the
process that holds the chip. The fields used, by number:

    XSpace.planes=1
    XPlane.name=2 .event_metadata=4 .stat_metadata=5   (maps: key=1 value=2)
    XEventMetadata.stats=5    XStatMetadata.name=2
    XStat.metadata_id=1 .bytes_value=6
    HloProto.hlo_module=1     HloModuleProto.computations=3
    HloComputationProto.instructions=2 .id=5
    HloInstructionProto.name=1 .opcode=2 .metadata=7 .called_computation_ids=38
    OpMetadata.op_name=2

A fusion is one event in the trace, so its time goes whole to one
region: that of the matrix multiplication or convolution it holds, or
else the one most of its instructions name. XLA fuses an optimizer's
arithmetic into the fusion that makes a gradient where it can; such a
fusion is the backward pass's, and ``look`` says how much of a region
has the update inside.

A reader is handed ``(trace, run)`` and not the file's path, so
``trace_file()`` finds the file again where ``run.py`` had the profiler
write it.
"""

from __future__ import annotations

import argparse
import collections
import functools
import os
import sys

from chipbench import setup_sources, xplane

# horovod_tpu.jax.REDUCE_SCOPE and UPDATE_SCOPE, spelled out: this file
# must also read the trace of a program that has neither
# (chipbench/tests/test_regions.py holds the two pairs equal).
REDUCE_SCOPE = "hvt_reduce_gradients"
UPDATE_SCOPE = "hvt_optimizer_update"

REGIONS = ("forward", "recompute", "backward", "reduce", "update",
           "unattributed")
# first match wins
_RULES = (("rematted_computation", "recompute"),
          ("transpose(jvp(", "backward"),
          (REDUCE_SCOPE, "reduce"),
          (UPDATE_SCOPE, "update"),
          ("jvp(", "forward"))


def naming_part(op_name: str):
    """Of one name stack, or of several joined by ``;``, the first part
    that names a region, and that region: ``(part, region)``."""
    for part in op_name.split(";"):
        for needle, found in _RULES:
            if needle in part:
                return part, found
    return "", "unattributed"


def region(op_name: str) -> str:
    return naming_part(op_name)[1]


# ---------------------------------------------------------- the wire format

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one serialized protobuf message: an
    int for a varint, a memoryview for a length-delimited or fixed-width
    field. Nothing is copied and nothing inside a field is parsed."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _first(buf, number, default=None):
    return next((v for n, v in fields(buf) if n == number), default)


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


def _varints(value):
    """A repeated integer field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_protos(space) -> list:
    """The serialized ``HloProto`` of every program in a serialized
    ``XSpace``: the ``Hlo Proto`` stats of plane ``/host:metadata``."""
    found = []
    for number, plane in fields(space):
        if number != 1 or _text(_first(plane, 2)) != "/host:metadata":
            continue
        stat_ids = set()
        for n, entry in fields(plane):
            if n == 5 and _text(_first(_first(entry, 2, b""), 2)) \
                    == "Hlo Proto":
                stat_ids.add(_first(entry, 1))
        for n, entry in fields(plane):
            if n != 4:
                continue
            for m, stat in fields(_first(entry, 2, b"")):
                if m == 5 and _first(stat, 1) in stat_ids:
                    proto = _first(stat, 6)
                    if proto is not None:
                        found.append(proto)
    return found


def program_names(hlo_proto) -> dict:
    """``{instruction name: op_name}`` over every computation of one
    serialized ``HloProto``. A fusion gets several, joined by ``;`` in
    the order in which they should be believed (``naming_part`` takes the
    first that names a region): the matrix multiplications and
    convolutions it holds; then its other instructions' names, those of
    the region most of them name first; its own last."""
    computations, instructions = {}, []
    for n, computation in fields(_first(hlo_proto, 1, b"")):
        if n != 3:
            continue
        inside, comp_id = [], None
        for m, value in fields(computation):
            if m == 5:
                comp_id = value
            elif m == 2:
                name = opcode = op_name = ""
                called = []
                for k, v in fields(value):
                    if k == 1:
                        name = _text(v)
                    elif k == 2:
                        opcode = _text(v)
                    elif k == 7:
                        op_name = _text(_first(v, 2))
                    elif k == 38:
                        called += _varints(v)
                inside.append((opcode, op_name))
                instructions.append((name, opcode, op_name, called))
        computations[comp_id] = inside
    names = {}
    for name, opcode, op_name, called in instructions:
        if opcode == "fusion":
            held = [(code, n) for c in called
                    for code, n in computations.get(c, ()) if n]
            where = {n: region(n) for _, n in held}
            votes = collections.Counter(where[n] for _, n in held)
            op_name = ";".join(dict.fromkeys(
                [n for code, n in held if code in ("dot", "convolution")]
                + sorted(where, key=lambda n: -votes[where[n]])
                + [op_name]))
        names[name] = op_name
    return names


@functools.lru_cache(maxsize=2)
def name_stacks(path: str):
    """``{instruction name (xplane.Op.name): op_name}`` for the programs
    of the trace at ``path``, or None where the trace holds no program.
    Where two programs have an instruction of one name the larger
    program's stands: the window holds the step program alone. Cached,
    since every reader below asks."""
    with open(path, "rb") as f:
        protos = hlo_protos(f.read())
    if not protos:
        return None
    names = {}
    for proto in sorted(protos, key=len):
        names.update(program_names(proto))
    return names


def trace_file(directory: str | None = None):
    """The newest ``.xplane.pb`` of the run: under ``directory``'s
    sub-directories (``run.py`` writes to ``<--trace-dir>/<cell>``), where
    ``directory`` is ``--trace-dir`` as ``sys.argv`` has it, else
    ``.chipbench_trace`` in the checkout. None when there is none. Exists
    because a reader is not told the path."""
    if directory is None:
        parser = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
        parser.add_argument("--trace-dir", default=os.path.join(
            setup_sources.CHECKOUT, ".chipbench_trace"))
        directory = parser.parse_known_args(sys.argv[1:])[0].trace_dir
    if not os.path.isdir(directory):
        return None
    found = [xplane.find(os.path.join(directory, sub))
             for sub in os.listdir(directory)]
    return max(filter(None, found), key=os.path.getmtime, default=None)


# ------------------------------------------------------------ the reduction

def _ops_ms(trace):
    """``[(op, ms a step inside chip 0's window)]`` for every operation
    that is not a collective; None without a window."""
    device = trace.devices[0]
    window = trace.window(device)
    if window is None:
        return None
    lo, hi, steps = window
    return [(op, (b - a) / steps / 1e6) for op in device.ops
            if op.kind != "collective"
            for a, b in xplane.clip([(op.start, op.end)], lo, hi)]


def region_ms(trace, names, scope: str = "") -> dict | None:
    """``{region: ms a step}`` over chip 0's window, for every operation
    that is not a collective (those are ``collective_ms``). ``XLA Ops``
    is one serial line, so the regions and ``collective_ms`` sum to
    ``step_busy_ms``. With ``scope``, only operations whose naming part
    holds it."""
    ops = _ops_ms(trace)
    if ops is None:
        return None
    out = dict.fromkeys(REGIONS, 0.0)
    for op, ms in ops:
        part, found = naming_part(names.get(op.name, ""))
        if scope in part:
            out[found] += ms
    return out


def read(trace, wanted, needs_scopes: bool = False, scope: str = ""):
    """What a reader returns: the summed ms a step of the regions
    ``wanted`` (of their operations under ``scope``, if given). None, so
    that the metric is left out, without a trace, a trace file or a
    program in it; and, for a metric that ``needs_scopes``, where no name
    holds ``UPDATE_SCOPE``: the program is the parent's (or a
    compile-cache entry with the parent's metadata: the cache's key leaves
    names out), its optimizer is unnamed, and what is left over would mean
    something else."""
    path = trace_file() if trace is not None else None
    names = name_stacks(path) if path else None
    if names is None:
        return None
    if needs_scopes and not any(UPDATE_SCOPE in n for n in names.values()):
        print(f"regions: no operation of {path} carries {UPDATE_SCOPE}: "
              f"not this package's program as it is now, or a cached "
              f"one compiled before the scopes", flush=True)
        return None
    split = region_ms(trace, names, scope)
    return None if split is None else sum(split[r] for r in wanted)


def look(trace, names, count: int = 8) -> dict:
    """A builder's look at chip 0's window, collectives left out as in
    ``region_ms``: for each region the ``count`` labels (``Op.label``)
    with most ms a step, and the ms a step of its fusions that also hold
    an instruction under ``UPDATE_SCOPE``. XLA fuses an optimizer's
    arithmetic into the fusion that makes the gradient, whose time cannot
    be divided, so this says how much of a region is "with the update
    inside" and ``update_ms`` is the update's alone."""
    labels = {r: {} for r in REGIONS}
    with_update = dict.fromkeys(REGIONS, 0.0)
    for op, ms in _ops_ms(trace):
        name = names.get(op.name, "")
        found = region(name)
        labels[found][op.label] = labels[found].get(op.label, 0.0) + ms
        with_update[found] += ms * (UPDATE_SCOPE in name)
    top = {r: sorted(map(list, found.items()), key=lambda kv: -kv[1])[:count]
           for r, found in labels.items()}
    return {"top_ops": top, "with_update_inside_ms": with_update}


if __name__ == "__main__":
    # python3 -m chipbench.regions [file.xplane.pb]: the last traced run
    # (or one file): the split, and what is in it
    import json

    path = sys.argv[1] if len(sys.argv) > 1 else trace_file()
    trace, names = xplane.load(path), name_stacks(path)
    print(json.dumps({"trace": path, "region_ms": region_ms(trace, names),
                      **look(trace, names)}))
