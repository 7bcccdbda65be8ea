"""From a profiler trace (``.xplane.pb``) to intervals a metric can read.
Uses ``jax.profiler.ProfileData`` and nothing else.

What the trace of a TPU v5e looks like (libtpu 0.0.34, looked at by hand
in PR 22): one plane ``/device:TPU:<i>`` a chip with the lines ``Steps``,
``XLA Modules`` (one event per execution of a compiled program) and
``XLA Ops`` (one event per HLO instruction the core ran, one after the
other; the event's name is the instruction's whole text, ``%name =
shape opcode(operands), attributes``). ``Async XLA Ops`` holds the
start-to-done spans of asynchronous copies, and only chip 0 has it. The
host's plane ``/host:CPU`` has a line a thread, named after it
(``python`` or ``python3`` for the main one), on which a
``StepTraceAnnotation`` appears under its own name with a ``step_num``.
Device and host clocks agree to about a millisecond, not better.
``ProfileData`` gives starts and durations in whole nanoseconds (the file
has picoseconds), so a sum over the 9,000 instructions of a 36-layer step
reads about 1e-5 short.

Classification is by the instruction's *opcode*, not its name: XLA names
an instruction after the JAX primitive or the flax scope it came from
(the package's gradient all-reduce is ``%psum_invariant.3 = ...
all-reduce(...)``, its Pallas kernels are ``%attn.8 = ... custom-call(...),
custom_call_target="tpu_custom_call"``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'

Interval = tuple  # (start_ns, end_ns)


@dataclasses.dataclass(frozen=True)
class Op:
    text: str           # the instruction as the trace names it
    start: float        # ns
    end: float          # ns
    kind: str           # classify(text), worked out once when loaded

    @property
    def name(self) -> str:
        return self.text.split(" = ", 1)[0].lstrip("%")

    @property
    def base(self) -> str:
        return re.sub(r"\.\d+$", "", self.name)

    @property
    def opcode(self) -> str:
        return opcode(self.text)

    @property
    def label(self) -> str:
        """What a breakdown calls it: the name without its number, the
        opcode and, for a fusion, XLA's kind (kLoop, kOutput, ...)."""
        fusion_kind = re.search(r", kind=(k\w+)", self.text)
        parts = [self.base, self.opcode] + (
            [fusion_kind.group(1)] if fusion_kind else [])
        return ":".join(parts)


def opcode(text: str) -> str:
    """``%n = shape opcode(...)`` -> ``opcode``; a tuple shape is in
    parentheses and may nest them."""
    if " = " not in text:
        return text
    rhs = text.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rhs = rhs[i + 1:].lstrip()
                break
    else:
        rhs = rhs.split(" ", 1)[1] if " " in rhs else rhs
    return rhs.split("(", 1)[0].strip()


def classify(text: str) -> str:
    """``collective``, ``kernel`` (a Pallas custom call) or ``compute``
    (everything else the core runs, copies included)."""
    code = opcode(text)
    for suffix in ("-start", "-done"):
        if code.endswith(suffix):
            code = code[:-len(suffix)]
    if code in COLLECTIVE_OPCODES:
        return "collective"
    if code == "custom-call" and PALLAS_TARGET in text:
        return "kernel"
    return "compute"


@dataclasses.dataclass
class Device:
    name: str
    ops: list           # [Op], sorted by start
    modules: list       # [(name, start_ns, end_ns)], sorted by start


@dataclasses.dataclass
class Trace:
    devices: list       # [Device], by chip number
    host_steps: list    # [(label, start_ns, end_ns)]: StepTraceAnnotations

    def window(self, device: Device):
        """(start, end, steps): from the start of the second execution
        of the step program to the start of the last one. Whole steps
        with the gaps between them; the first is left out because the
        profiler's own start precedes it."""
        names = [m[0] for m in device.modules]
        if not names:
            return None
        step_program = max(set(names), key=names.count)
        starts = [m[1] for m in device.modules if m[0] == step_program]
        if len(starts) < 3:
            return None
        return starts[1], starts[-1], len(starts) - 2

    def in_window(self, device: Device, kind: str | None = None):
        """Intervals of ``device``'s ops (of one kind, or all) clipped to
        its window, and the number of steps the window holds."""
        w = self.window(device)
        if w is None:
            return [], 0
        lo, hi, steps = w
        ops = [o for o in device.ops if kind is None or o.kind == kind]
        return clip([(o.start, o.end) for o in ops], lo, hi), steps


def union(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes) -> list:
    """The part of ``union(intervals)`` that no interval of ``holes``
    covers."""
    out, holes = [], union(holes)
    for lo, hi in union(intervals):
        for h_lo, h_hi in holes:
            if h_hi <= lo or h_lo >= hi:
                continue
            if h_lo > lo:
                out.append((lo, h_lo))
            lo = max(lo, h_hi)
            if lo >= hi:
                break
        if lo < hi:
            out.append((lo, hi))
    return out


def gaps(intervals, lo, hi) -> list:
    """The idle intervals of a window: what ``union(intervals)`` leaves
    of ``(lo, hi)``."""
    return subtract([(lo, hi)], intervals)


def find(directory: str) -> str | None:
    """The newest ``.xplane.pb`` the profiler wrote under ``directory``."""
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(data) -> Trace:
    """``data``: a ``jax.profiler.ProfileData``."""
    devices, host_steps = [], []
    for plane in data.planes:
        chip = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if chip:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Op(e.name, e.start_ns, e.start_ns + e.duration_ns,
                              classify(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(re.sub(r"\(\d+\)$", "", e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events]
            devices.append((int(chip.group(1)), Device(
                plane.name, sorted(ops, key=lambda o: o.start),
                sorted(modules, key=lambda m: m[1]))))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "step_num" in stats:
                        host_steps.append(
                            (f"{e.name}#{stats['step_num']}", e.start_ns,
                             e.start_ns + e.duration_ns))
    return Trace([d for _, d in sorted(devices, key=lambda x: x[0])],
                 sorted(host_steps, key=lambda s: s[1]))


# ------------------------------------------------------------ reductions

def busy_and_window_seconds(trace: Trace):
    """Seconds in which an operation ran, and the length of the traced
    window, both averaged over the chips; None if no chip has a window."""
    busy, length = [], []
    for device in trace.devices:
        w = trace.window(device)
        if w is None:
            continue
        intervals, _ = trace.in_window(device)
        busy.append(total(union(intervals)) / 1e9)
        length.append((w[1] - w[0]) / 1e9)
    if not busy:
        return None
    return sum(busy) / len(busy), sum(length) / len(length)


def breakdown(trace: Trace, top_ops: int = 10, top_gaps: int = 5) -> dict:
    """Chip 0's window: the operations with most time, by ``Op.label``,
    and the longest idle gaps, each with the host step annotation that
    covers its middle (clocks agree to about a millisecond)."""
    device = trace.devices[0]
    w = trace.window(device)
    if w is None:
        return {"device_ops": [], "idle_gaps": []}
    lo, hi, _ = w
    by_name = {}
    for o in device.ops:
        for a, b in clip([(o.start, o.end)], lo, hi):
            by_name[o.label] = by_name.get(o.label, 0.0) + (b - a) / 1e9
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top_ops]
    idle = sorted(gaps([(o.start, o.end) for o in device.ops], lo, hi),
                  key=lambda g: g[0] - g[1])[:top_gaps]

    def covering(t):
        for label, a, b in trace.host_steps:
            if a <= t <= b:
                return label
        return "host_not_in_a_step_call"

    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[covering((a + b) / 2), (b - a) / 1e9]
                          for a, b in idle]}
