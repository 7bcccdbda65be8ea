"""A job's start, measured where it happens.

Two sources, one recorder, on whenever ``hvt.init()`` has run:

- **phases of the package's own start**: :func:`span` around the import
  of ``horovod_tpu`` and around ``hvt.init()`` and its parts. Each is a
  ``jax.profiler.TraceAnnotation("hvt_startup/<phase>")`` (free unless a
  profiler session covers the start) and a record ``(phase, parent,
  start, end)`` here, served as ``hvt_startup_seconds{phase}``.
- **what JAX reports of every program**: a ``jax.monitoring`` listener
  (:func:`listen`, taken off by :func:`stop_listening`) on the trace,
  lower and ``backend_compile`` time spans and on the persistent cache's
  retrieval time, hits and misses. JAX's events nest (an inner ``jit`` is
  traced inside the outer trace, thousands of times in one model; a
  cache read happens inside ``backend_compile``), so a span's *self*
  seconds are its duration less what the spans inside it hold: every
  second goes to the innermost span, and the stages' seconds add up to
  the time JAX spent staging programs, none of it twice. A span's parent
  is the span that encloses it; when the parent arrives, what it
  encloses is folded into it, so memory holds the outermost spans only.
  Served as ``hvt_jax_stage_seconds_total{stage}``,
  ``hvt_jax_stage_events_total{stage}``,
  ``hvt_jax_cache_reads_total{result}``.

:func:`report` (``hvt.startup_report``) is the reader for people;
``chipbench/layer_metrics/{init_s,devices_s,trace_s,lower_s,cache_read_s,
setup_unnamed_s}.py`` are the benchmark's; :func:`collect` feeds the
registry behind ``/metrics`` when it is scraped. Clock: ``time.time()``,
which JAX stamps its events with and ``utils/timeline.py`` writes.
"""

from __future__ import annotations

import bisect
import collections
import threading
import time
from typing import Optional

PREFIX = "hvt_startup/"
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# outermost spans held: a start is a few hundred (one model's step is
# one trace, one lowering, one compile, whatever they enclose); a job
# that stages programs for days must not grow without bound
MAX_SPANS = 4096


def _add(into: dict, other: dict):
    for key, values in other.items():
        mine = into.get(key)
        if mine is None:
            into[key] = list(values)
        else:
            for i, v in enumerate(values):
                mine[i] += v


def _fold(stages: dict, names: dict, record):
    """Add a held record to ``{stage: [self seconds, spans]}`` and
    ``{fun_name: [trace + lower seconds, traced, lowered, compiled]}``.
    A record is ``(start, end, stage, fun_name, self seconds)`` for a span
    that encloses nothing (nine in ten: one allocation an event, of
    nothing the garbage collector has to follow) and ``(start, end,
    stages, names)`` for one with what it encloses folded in."""
    if len(record) == 4:
        _add(stages, record[2])
        _add(names, record[3])
        return
    start, end, stage, name, own = record
    _add(stages, {stage: (own, 1)})
    if stage == "backend_compile":
        _add(names, {name: (0.0, 0, 0, 1)})
    elif stage != "cache_read":
        _add(names, {name: (end - start, int(stage == "trace"),
                            int(stage == "lower"), 0)})


class Recorder:
    """Phases and JAX's outermost stage spans in memory, and the totals
    of what the cap turned away."""

    def __init__(self, max_spans: int = MAX_SPANS):
        self.max_spans = max_spans
        self.lock = threading.Lock()
        self.phases = []        # (phase, parent, start, end)
        self.open_phases = []   # entered and not yet left
        self.spans = []         # outermost spans, as _fold reads them
        self.past_cap = {}      # the stages of the spans not held
        self.spans_dropped = 0
        self.compiled_last = collections.deque(maxlen=10)
        self.cache = {"hit": 0, "miss": 0}
        self.events = 0         # monitoring events handled here, and
        self.listener_seconds = 0.0     # what handling them took
        # disjoint, sorted: the seconds some span already holds
        self._held_starts, self._held_ends = [], []

    def first_timestamp(self) -> Optional[float]:
        starts = [p[2] for p in self.phases] + [s[0] for s in self.spans[:1]]
        return min(starts) if starts else None

    def add_phase(self, phase, parent, start, end):
        with self.lock:
            self.phases.append((phase, parent, start, end))

    def add_span(self, stage, fun_name, start, end) -> float:
        """Take one stage span; returns its self seconds. JAX reports a
        span when it ends, so what it encloses has arrived before it and
        lies at the tail: it is folded into this one."""
        # the trace reports ``step``, lowering and compiling ``jit(step)``
        name = str(fun_name)
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        with self.lock:
            own = self._claim(start, end)
            record = (start, end, stage, name, own)
            if stage == "backend_compile":
                self.compiled_last.append(
                    {"fun_name": name, "at": end, "seconds": end - start})
            spans = self.spans
            if spans and spans[-1][0] >= start:
                stages, names = {}, {}
                _fold(stages, names, record)
                while spans and spans[-1][0] >= start:
                    _fold(stages, names, spans.pop())
                record = (start, end, stages, names)
            if len(spans) < self.max_spans:
                spans.append(record)
            else:
                stages = {}
                _fold(stages, {}, record)
                _add(self.past_cap, stages)
                self.spans_dropped += sum(n for _, n in stages.values())
            return own

    def _claim(self, start, end) -> float:
        """Seconds of ``[start, end]`` that no span holds yet; they are
        this span's from now on. An inner span ends, and so arrives,
        before the one around it, which is left with the rest."""
        starts, ends = self._held_starts, self._held_ends
        lo = bisect.bisect_left(ends, start)
        hi = bisect.bisect_right(starts, end)
        if lo == hi:        # nothing of it is held: nine spans in ten
            starts.insert(lo, start)
            ends.insert(lo, end)
            free = end - start
        else:
            free = end - start - sum(
                min(ends[i], end) - max(starts[i], start)
                for i in range(lo, hi))
            starts[lo:hi] = [min(start, starts[lo])]
            ends[lo:hi] = [max(end, ends[hi - 1])]
        if len(starts) > self.max_spans:    # programs long finished
            del starts[0], ends[0]
        return max(free, 0.0)


_recorder = Recorder()
_listening = False
# "time_span", or "duration" where this JAX has no time-span listener and
# the stages' spans are stamped on arrival; None before the first init
_listener_kind = None


def recorder() -> Recorder:
    return _recorder


class span:
    """``with span("init"):`` — one phase of the package's start."""

    def __init__(self, phase: str):
        self.phase = phase

    def __enter__(self):
        self.start = time.time()
        open_phases = _recorder.open_phases
        self.parent = open_phases[-1] if open_phases else None
        open_phases.append(self.phase)
        import jax

        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.phase)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        _recorder.open_phases.remove(self.phase)
        _recorder.add_phase(self.phase, self.parent, self.start, time.time())


def _on_stage(stage, start, end, fun_name):
    t0 = time.perf_counter()
    _recorder.add_span(stage, fun_name, start, end)
    _recorder.events += 1
    _recorder.listener_seconds += time.perf_counter() - t0


def _on_time_span(event, start_time, end_time, fun_name=None, **_):
    stage = STAGES.get(event)
    if stage is not None:
        _on_stage(stage, start_time, end_time, fun_name)


def _on_duration(event, duration_secs, fun_name=None, **_):
    """The cache's retrieval time comes as a duration only: it is stamped
    on arrival, which is inside the ``backend_compile`` span that it is
    part of. On a JAX without time spans the three stages come this way
    too."""
    stage = STAGES.get(event)
    if stage is not None and (stage == "cache_read"
                              or _listener_kind == "duration"):
        now = time.time()
        _on_stage(stage, now - duration_secs, now, fun_name)


def _on_event(event, **_):
    result = CACHE_EVENTS.get(event)
    if result is not None:
        with _recorder.lock:
            _recorder.cache[result] += 1
        _recorder.events += 1


def listen():
    """Register the listener (``hvt.init()``); a second call is a no-op."""
    global _listening, _listener_kind
    if _listening:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    if hasattr(monitoring, "register_event_time_span_listener"):
        monitoring.register_event_time_span_listener(_on_time_span)
        _listener_kind = "time_span"
    else:
        _listener_kind = "duration"
    _listening = True


def stop_listening():
    """Take the listener off (``hvt.shutdown()``). What was recorded
    stays, for a report after the job."""
    global _listening
    if not _listening:
        return
    from jax import monitoring

    monitoring.unregister_event_duration_listener(_on_duration)
    monitoring.unregister_event_listener(_on_event)
    if _listener_kind == "time_span":
        monitoring.unregister_event_time_span_listener(_on_time_span)
    _listening = False


def report(until: Optional[float] = None, rec: Optional[Recorder] = None):
    """What this process's start was made of, as a dict.

    - ``phases``: the package's own spans, in order, each with its parent;
    - ``stages``: for trace, lower, backend_compile and cache_read the
      self seconds (a nested second goes to the inner stage, so they add
      up) and the number of spans;
    - ``cache``: the persistent cache's hits and misses;
    - ``functions``: the ten names with most trace + lower seconds
      (inclusive), each with how often it was traced, lowered and
      compiled: a block traced 36 times shows here;
    - ``compiled_last``: the ten newest ``backend_compile`` spans, name
      and time: in a running job, the function that recompiled at step N;
    - ``spans_dropped``: spans past the cap, in the stages' totals only.

    ``until`` (seconds since the epoch) keeps what ended by then.
    """
    rec = rec or _recorder
    with rec.lock:
        phases, spans = list(rec.phases), list(rec.spans)
        past_cap, cache = dict(rec.past_cap), dict(rec.cache)
        compiled = list(rec.compiled_last)
        spans_dropped = rec.spans_dropped
    if until is not None:   # the cap turns away the latest, past any cut
        phases = [p for p in phases if p[3] <= until]
        spans = [s for s in spans if s[1] <= until]
        compiled = [c for c in compiled if c["at"] <= until]
        past_cap = {}
    stages = {stage: [0.0, 0] for stage in STAGES.values()}
    _add(stages, past_cap)
    names = {}
    for record in spans:
        _fold(stages, names, record)
    import jax

    return {
        "jax": jax.__version__,
        "listener": _listener_kind,
        "phases": [{"phase": p, "parent": parent, "start": start,
                    "seconds": end - start}
                   for p, parent, start, end in sorted(
                       phases, key=lambda p: p[2])],
        "stages": {stage: {"seconds": seconds, "events": events}
                   for stage, (seconds, events) in stages.items()},
        "cache": cache,
        "functions": [
            {"fun_name": name, "seconds": seconds, "trace": traced,
             "lower": lowered, "backend_compile": compiled_n}
            for name, (seconds, traced, lowered, compiled_n) in sorted(
                names.items(), key=lambda item: -item[1][0])[:10]],
        "compiled_last": compiled,
        "spans_dropped": spans_dropped,
        "listener_events": rec.events,
        "listener_seconds": rec.listener_seconds,
    }


def collect(registry):
    """The recorder as series, set when ``/metrics`` is scraped
    (``metrics.registry()`` installs this beside the engine's)."""
    found = report()
    gauge = registry.gauge(
        "hvt_startup_seconds",
        "wall seconds of one phase of this process's last start (import, "
        "init, and init's parts)", ("phase",))
    for phase in found["phases"]:
        gauge.labels(phase["phase"]).set(phase["seconds"])
    seconds = registry.counter(
        "hvt_jax_stage_seconds_total",
        "seconds JAX spent staging programs, by stage (trace, lower, "
        "backend_compile, cache_read); nested spans counted once, to the "
        "inner stage", ("stage",))
    events = registry.counter(
        "hvt_jax_stage_events_total",
        "spans JAX reported, by stage; backend_compile rising after "
        "warm-up is a recompile", ("stage",))
    for stage, total in found["stages"].items():
        seconds.labels(stage).set_total(total["seconds"])
        events.labels(stage).set_total(total["events"])
    reads = registry.counter(
        "hvt_jax_cache_reads_total",
        "persistent compilation cache lookups, by result (hit, miss)",
        ("result",))
    for result, count in found["cache"].items():
        reads.labels(result).set_total(count)


def format_report(r: dict) -> str:
    """The report as the few lines ``HVT_VERBOSE`` prints at shutdown."""
    lines = [f"[hvt] start (jax {r['jax']}, listener {r['listener']}):"]
    for p in r["phases"]:
        indent = "    " if p["parent"] else "  "
        lines.append(f"{indent}{p['phase']:<18}{p['seconds']:9.3f} s")
    for stage, t in r["stages"].items():
        lines.append(f"  jax {stage:<16}{t['seconds']:9.3f} s in "
                     f"{t['events']} span(s)")
    lines.append(f"  cache: {r['cache']['hit']} hit(s), "
                 f"{r['cache']['miss']} miss(es)")
    for f in r["functions"]:
        lines.append(
            f"  {f['seconds']:9.3f} s trace + lower  {f['fun_name']}  "
            f"(traced {f['trace']}x, lowered {f['lower']}x, compiled "
            f"{f['backend_compile']}x)")
    for c in r["compiled_last"]:
        stamp = time.strftime("%H:%M:%S", time.localtime(c["at"]))
        lines.append(f"  compiled {stamp}  {c['seconds']:9.3f} s  "
                     f"{c['fun_name']}")
    if r["spans_dropped"]:
        lines.append(f"  {r['spans_dropped']} span(s) past the cap are in "
                     f"the stages' totals only")
    return "\n".join(lines)
