"""Chip 0's window read once, event by event: which Pallas kernel an event
is a call of, under which ``jax.named_scope`` it ran, in which pass, and
for how long. What the readers that take a kernel's calls apart, or a
mixer's scopes, share (``layer_metrics/attn_ms.py``, ``flash_fwd_ms.py``,
``moe_rounds.py`` and their neighbours), and what
``benchmarks/trace_by_scope.py`` counts a kernel's calls with.

An event is kept with what identifies it in the compiled step: the
instruction's base name (``xplane.Op.base``: ``hvt_flash_fwd``, ``gmm``,
``fusion``), the instruction itself (``Op.name``, ``gmm.7``: two events
of one name are two executions of one instruction, as a loop's body
gives), the part of its name stack that names a pass and that pass
(``regions.naming_part``: ``forward``, ``recompute``, ``backward``, ...),
and its nanoseconds inside the window. Collectives are left out, as
``regions.region_ms`` leaves them to ``collective_ms``; so are ``while``
and ``conditional`` events, as ``dsa_ms.under`` and
``benchmarks/trace_by_scope.py`` leave them out: a container's event
spans its body's, whose events are on the line too.

One walk a run: the events are kept in the dict ``run.py`` hands every
reader, so that nine readers cost one pass over the line.
"""

from __future__ import annotations

import collections
import dataclasses

from chipbench import regions, xplane

CONTAINERS = ("while", "conditional")
KEPT = "kernel_calls.window"    # where a run's dict keeps its walk


@dataclasses.dataclass(frozen=True)
class Event:
    base: str       # the instruction's name without its number
    name: str       # the instruction this event is an execution of
    kernel: bool    # a Pallas custom call
    part: str       # the part of its name stack that names a pass
    region: str     # that pass (one of regions.REGIONS)
    ns: float       # inside the window


@dataclasses.dataclass(frozen=True)
class Window:
    steps: int
    events: tuple   # (Event, ...) in the order of the line


def walk(trace, names) -> Window | None:
    """Chip 0's window as ``Event``s; None without a window."""
    device = trace.devices[0]
    window = trace.window(device)
    if window is None:
        return None
    lo, hi, steps = window
    events = []
    for op in device.ops:
        ns = min(op.end, hi) - max(op.start, lo)
        if ns <= 0 or op.kind == "collective" or op.opcode in CONTAINERS:
            continue
        part, region = regions.naming_part(names.get(op.name, ""))
        events.append(Event(op.base, op.name, op.kind == "kernel", part,
                            region, ns))
    return Window(steps, tuple(events))


def window(trace, run) -> Window | None:
    """The window of the run's trace with the names of the program in its
    file; None where there is no trace, no trace file, no program in it
    or no window. Kept in ``run``, the dict ``run.py`` hands every reader,
    so a run's trace is walked, and its file looked for, once."""
    if trace is None:
        return None
    kept = run.get(KEPT)
    if kept is None or kept[0] is not trace:
        path = regions.trace_file()
        names = regions.name_stacks(path) if path else None
        kept = run[KEPT] = (trace, walk(trace, names) if names else None)
    return kept[1]


def _calls(trace, run, bases, region=None):
    found = window(trace, run)
    if found is None:
        return None, []
    return found, [e for e in found.events if e.kernel and e.base in bases
                   and region in (None, e.region)]


def kernel_ms(trace, run, *bases):
    """ms a step of the Pallas calls whose base name is one of ``bases``,
    whatever their scope and pass; None where there is none."""
    found, calls = _calls(trace, run, bases)
    return sum(e.ns for e in calls) / found.steps / 1e6 if calls else None


def call_ms(trace, run, base, region):
    """Mean ms of one call of the kernel ``base`` in the pass ``region``;
    None where it has no such call."""
    _, calls = _calls(trace, run, (base,), region)
    return sum(e.ns for e in calls) / len(calls) / 1e6 if calls else None


def scope_ms(trace, run, scopes):
    """ms a step of the events whose naming part holds one of ``scopes``
    (``"/attn_core/"``), forward, recomputed and backward together; None
    where there is none."""
    found = window(trace, run)
    if found is None:
        return None
    return sum(e.ns for e in found.events
               if any(s in e.part for s in scopes)) / found.steps / 1e6 \
        or None


def executions(trace, run, base):
    """``(executions a step of an instruction, most for one instruction,
    instructions)`` of the kernel ``base``: its events in the window over
    its distinct instructions and the steps. 1.0 where every instruction
    ran once a step; a loop whose body holds the kernel gives its trips.
    None where it has no call."""
    found, calls = _calls(trace, run, (base,))
    if not calls:
        return None
    by_name = collections.Counter(e.name for e in calls)
    return (len(calls) / len(by_name) / found.steps,
            max(by_name.values()) / found.steps, len(by_name))


def calls_a_step(found: Window) -> dict:
    """``{base name: calls a step}`` of every Pallas kernel."""
    counts = collections.Counter(e.base for e in found.events if e.kernel)
    return {base: n / found.steps for base, n in counts.items()}


if __name__ == "__main__":
    # python3 -m chipbench.kernel_calls [file.xplane.pb]: the last traced
    # run (or one file): every kernel's calls a step, ms a step and ms a
    # call by pass, and the seconds the walk took
    import json
    import sys
    import time

    path = sys.argv[1] if len(sys.argv) > 1 else regions.trace_file()
    trace, names = xplane.load(path), regions.name_stacks(path)
    t0 = time.perf_counter()
    found = walk(trace, names)
    seconds = time.perf_counter() - t0
    by_pass = {}
    for e in found.events:
        if e.kernel:
            n, ns = by_pass.setdefault(e.base, {}).get(e.region, (0, 0.0))
            by_pass[e.base][e.region] = (n + 1, ns + e.ns)
    print(json.dumps({
        "trace": path, "steps": found.steps, "events": len(found.events),
        "walk_s": seconds, "kernel_calls_a_step": calls_a_step(found),
        "ms_a_call_by_pass": {
            base: {region: ns / n / 1e6 for region, (n, ns) in split.items()}
            for base, split in by_pass.items()}}))
