"""A job's start as the package records it (``metrics/startup.py``): the
phases of ``hvt.init()``, JAX's trace / lower / compile / cache-read
spans counted once, ``hvt.startup_report()``, and the series behind
``/metrics``."""

import glob
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

import horovod_tpu as hvt
from horovod_tpu import metrics
from horovod_tpu.metrics import startup

TRACE, LOWER, COMPILE, CACHE = startup.STAGES


# ---- synthetic spans: no second twice, and it goes to the inner stage

def filled(spans, **kwargs):
    """A recorder given ``(stage, name, start, end)`` in the order JAX
    would report them: by their ends."""
    rec = startup.Recorder(**kwargs)
    own = [rec.add_span(*s) for s in sorted(spans, key=lambda s: s[3])]
    return rec, own


@pytest.mark.parametrize("spans, want", [
    # side by side: each its own
    ([("trace", "a", 0, 2), ("lower", "a", 2, 5)],
     {"trace": 2, "lower": 3}),
    # an inner jit traced inside the outer trace: one stage, counted once
    ([("trace", "step", 0, 10), ("trace", "_fwd_call", 2, 5)],
     {"trace": 10}),
    # a Pallas body lowered inside the outer lowering, twice
    ([("lower", "step", 0, 10), ("lower", "body", 1, 3),
      ("lower", "body", 6, 8)], {"lower": 10}),
    # two stages overlap: the inner one wins the second
    ([("lower", "step", 0, 10), ("trace", "kernel", 4, 7)],
     {"lower": 7, "trace": 3}),
    # a cache read inside backend_compile
    ([("backend_compile", "step", 0, 5), ("cache_read", None, 0.5, 4.5)],
     {"backend_compile": 1, "cache_read": 4}),
    # three deep, and a neighbour
    ([("trace", "a", 0, 12), ("lower", "b", 2, 10), ("trace", "c", 3, 4),
      ("backend_compile", "d", 12, 20)],
     {"trace": 5, "lower": 7, "backend_compile": 8}),
    # two threads' spans that cross without nesting: still no second twice
    ([("trace", "a", 0, 6), ("trace", "b", 4, 9)], {"trace": 9}),
])
def test_every_second_once_and_to_the_inner_stage(spans, want):
    rec, _ = filled(spans)
    stages = startup.report(rec=rec)["stages"]
    got = {s: t["seconds"] for s, t in stages.items() if t["events"]}
    assert got == pytest.approx(want)
    lo = min(s[2] for s in spans)
    hi = max(s[3] for s in spans)
    assert sum(got.values()) <= hi - lo + 1e-9
    assert sum(t["events"] for t in stages.values()) == len(spans)


def test_self_time_is_duration_less_children():
    spans = [("trace", "outer", 0, 10), ("trace", "child", 1, 4),
             ("lower", "child", 5, 7), ("trace", "grandchild", 2, 3)]
    _, own = filled(spans)
    by_name = dict(zip(
        [(s[0], s[1]) for s in sorted(spans, key=lambda s: s[3])], own))
    assert by_name["trace", "grandchild"] == pytest.approx(1)
    assert by_name["trace", "child"] == pytest.approx(3 - 1)
    assert by_name["lower", "child"] == pytest.approx(2)
    assert by_name["trace", "outer"] == pytest.approx(10 - 3 - 2)


def test_until_leaves_out_what_ended_later():
    rec, _ = filled([("trace", "a", 0, 2), ("trace", "b", 3, 4)])
    rec.add_phase("init", None, 0.0, 1.0)
    rec.add_phase("late", None, 5.0, 6.0)
    cut = startup.report(until=2.5, rec=rec)
    assert cut["stages"]["trace"] == {"seconds": pytest.approx(2),
                                      "events": 1}
    assert [p["phase"] for p in cut["phases"]] == ["init"]
    assert rec.first_timestamp() == 0.0


def test_what_a_span_encloses_is_folded_into_it():
    # a model's trace: thousands of inner jits under 36 blocks under one
    # step. Memory holds the outermost span; the report loses nothing
    spans, t = [], 0.0
    for block in range(36):
        start = t
        for inner in range(100):
            spans.append(("trace", f"op{inner % 7}", t, t + 0.5))
            t += 1
        spans.append(("trace", "block", start, t))
        t += 1
    spans += [("trace", "step", -1, t), ("lower", "jit(step)", t, t + 5)]
    rec, _ = filled(spans)
    assert len(rec.spans) == 2 and len(rec._held_starts) == 1
    report = startup.report(rec=rec)
    assert report["spans_dropped"] == 0
    assert report["stages"]["trace"] == {"seconds": pytest.approx(t + 1),
                                         "events": 3600 + 36 + 1}
    by_name = {f["fun_name"]: f for f in report["functions"]}
    assert by_name["block"]["trace"] == 36
    assert by_name["step"]["seconds"] == pytest.approx(t + 1 + 5)
    assert by_name["op0"]["trace"] == 36 * 15


def test_the_cap_holds_and_the_report_says_what_it_dropped():
    # ten programs side by side, each a trace with another inside it
    spans = [s for i in range(10) for s in
             [("trace", f"f{i}", 2 * i, 2 * i + 1),
              ("trace", "inner", 2 * i + 0.25, 2 * i + 0.5)]]
    rec, _ = filled(spans, max_spans=4)
    assert len(rec.spans) == 4
    assert len(rec._held_starts) <= 5
    report = startup.report(rec=rec)
    assert report["spans_dropped"] == 12
    # the totals go on past the cap
    assert report["stages"]["trace"] == {"seconds": pytest.approx(10),
                                         "events": 20}
    assert len(report["functions"]) == 4 + 1
    assert "12 span(s) past the cap" in startup.format_report(report)
    # a cut lies before what the cap turned away
    assert startup.report(until=7.5, rec=rec)["stages"]["trace"] == {
        "seconds": pytest.approx(4), "events": 8}


def test_the_report_names_what_was_traced_most_and_how_often():
    spans = [("trace", "block", i, i + 0.5) for i in range(36)]
    spans += [("trace", "step", 100, 101), ("lower", "jit(step)", 101, 103),
              ("backend_compile", "jit(step)", 103, 104)]
    rec, _ = filled(spans)
    first, second = startup.report(rec=rec)["functions"][:2]
    assert first == {"fun_name": "block", "seconds": pytest.approx(18),
                     "trace": 36, "lower": 0, "backend_compile": 0}
    # the trace says ``step``, lowering and compiling ``jit(step)``
    assert second == {"fun_name": "step", "seconds": pytest.approx(3),
                      "trace": 1, "lower": 1, "backend_compile": 1}


# ---- hvt.init(): phases, the gauge, the listener

def test_init_leaves_its_phases_with_devices_inside_init():
    phases = {p["phase"]: p for p in hvt.startup_report()["phases"]}
    assert {"import", "init", "devices", "process_sets"} <= set(phases)
    init, devices = phases["init"], phases["devices"]
    assert devices["parent"] == "init" and init["parent"] is None
    assert init["start"] <= devices["start"]
    assert (devices["start"] + devices["seconds"]
            <= init["start"] + init["seconds"])
    assert phases["import"]["start"] + phases["import"]["seconds"] \
        <= init["start"]
    # what did not run recorded nothing: one process, no launcher
    assert not {"distributed_join", "engine", "endpoints"} & set(phases)
    assert startup.recorder().first_timestamp() == phases["import"]["start"]


def test_startup_seconds_are_in_the_prometheus_text():
    text = metrics.prometheus_text()
    assert "# TYPE hvt_startup_seconds gauge" in text
    for phase in ("import", "init", "devices", "process_sets"):
        assert f'hvt_startup_seconds{{phase="{phase}"}}' in text
    snapshot = metrics.json_snapshot()
    assert {s["labels"]["stage"] for s in
            snapshot["hvt_jax_stage_seconds_total"]["samples"]} == set(
                startup.STAGES.values())


def listeners():
    return (len(jax_monitoring.get_event_listeners()),
            len(jax_monitoring.get_event_duration_listeners()),
            len(jax_monitoring.get_event_time_span_listeners()))


def test_init_shutdown_init_leaves_the_listeners_where_they_were():
    before = listeners()
    n_phases = len(startup.recorder().phases)
    try:
        hvt.shutdown()
        assert listeners() == tuple(n - 1 for n in before)
        hvt.shutdown()                      # a second one takes off nothing
        assert listeners() == tuple(n - 1 for n in before)
    finally:
        hvt.init()
    hvt.init()
    assert listeners() == before
    # what the first start recorded is still there, beside the second's
    assert len(startup.recorder().phases) == n_phases + 3


def test_verbose_shutdown_prints_the_report(monkeypatch, capsys):
    monkeypatch.setenv("HVT_VERBOSE", "1")
    try:
        hvt.shutdown()
    finally:
        hvt.init()
    out = capsys.readouterr().out
    assert "[hvt] start (jax " in out and "devices" in out
    assert "jax backend_compile" in out


# ---- JAX's own events: a compile, no recompile, a recompile

def stage_events():
    report = hvt.startup_report()
    return ({s: t["events"] for s, t in report["stages"].items()},
            report["compiled_last"])


def test_a_new_jit_compiles_once_and_a_new_shape_once_more():
    def a_function_of_this_test(x):
        return jnp.sin(x) * 2

    f = jax.jit(a_function_of_this_test)
    three, four = jnp.ones(3), jnp.ones(4)  # their own programs, up front
    before, _ = stage_events()
    f(three)
    first, compiled = stage_events()
    assert first["backend_compile"] == before["backend_compile"] + 1
    assert first["trace"] > before["trace"]
    assert first["lower"] == before["lower"] + 1
    assert compiled[-1]["fun_name"] == "a_function_of_this_test"
    f(three)                                # the same shape: nothing
    assert stage_events()[0] == first
    f(four)                                 # what an operator looks for
    again, compiled = stage_events()
    assert again["backend_compile"] == first["backend_compile"] + 1
    assert [c["fun_name"] for c in compiled[-2:]] == \
        ["a_function_of_this_test"] * 2
    ours = [f for f in hvt.startup_report()["functions"]
            if f["fun_name"] == "a_function_of_this_test"]
    assert not ours or ours[0]["backend_compile"] == 2
    text = metrics.prometheus_text()
    assert 'hvt_jax_stage_events_total{stage="backend_compile"}' in text
    assert 'hvt_jax_stage_seconds_total{stage="trace"}' in text


def test_hit_and_miss_against_a_temporary_cache(tmp_path):
    from jax._src import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    kept = {name: getattr(jax.config, name) for name in names}
    f = jax.jit(lambda x: jnp.cos(x) + 17)
    try:
        for name, value in zip(names, (str(tmp_path), 0.0, -1)):
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        x = jnp.ones(5)
        before = hvt.startup_report()
        f(x)
        cold = hvt.startup_report()
        jax.clear_caches()
        f(x)
    finally:
        for name, value in kept.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    after = hvt.startup_report()
    # nothing to read on the first call, nothing to compile on the second
    assert cold["cache"] == {"hit": before["cache"]["hit"],
                             "miss": before["cache"]["miss"] + 1}
    assert after["cache"] == {"hit": cold["cache"]["hit"] + 1,
                              "miss": cold["cache"]["miss"]}
    reads = [r["stages"]["cache_read"] for r in (before, cold, after)]
    assert reads[1] == reads[0]
    assert reads[2]["events"] == reads[1]["events"] + 1
    assert reads[2]["seconds"] > reads[1]["seconds"]
    text = metrics.prometheus_text()
    assert 'hvt_jax_cache_reads_total{result="hit"}' in text
    assert 'hvt_jax_cache_reads_total{result="miss"}' in text


def test_without_time_spans_the_durations_are_stamped_on_arrival(
        monkeypatch):
    # a recorder of its own: the process's may be at its cap of spans by
    # now (whatever ran before in this worker), and then holds no new one
    monkeypatch.setattr(startup, "_recorder", startup.Recorder())
    monkeypatch.setattr(startup, "_listener_kind", "duration")
    before = hvt.startup_report()["stages"]["lower"]
    startup._on_duration(LOWER, 0.25, fun_name="jit(old_jax)")
    record = startup.recorder().spans[-1]
    (start, end), stages, names = record[:2], {}, {}
    startup._fold(stages, names, record)
    assert end - start == pytest.approx(0.25)
    assert abs(end - time.time()) < 5
    assert names["old_jax"][2] == 1 and 0 <= stages["lower"][0] <= 0.25 + 1e-6
    report = hvt.startup_report()
    assert report["listener"] == "duration"
    assert report["stages"]["lower"]["events"] == before["events"] + 1
    # with time spans, the same event's duration is left to them
    monkeypatch.setattr(startup, "_listener_kind", "time_span")
    startup._on_duration(LOWER, 0.25, fun_name="jit(old_jax)")
    assert hvt.startup_report()["stages"]["lower"] == report["stages"]["lower"]


def test_what_the_listener_costs_an_event():
    # a start fires a few hundred events; each must stay in microseconds
    rec = startup.recorder()
    events, seconds = rec.events, rec.listener_seconds
    now = time.time()       # as JAX stamps them; each inside the last
    t0 = time.perf_counter()
    for i in range(1000):
        startup._on_time_span(TRACE, now - 1e-3 * i, now + 1e-3 * i,
                              fun_name=f"timed{i % 50}")
        startup._on_time_span("/jax/some/other/event", 0.0, 1.0)
    elapsed = time.perf_counter() - t0
    assert rec.events == events + 1000
    assert 0 < rec.listener_seconds - seconds <= elapsed
    print(f"listener: {elapsed / 1000 * 1e6:.1f} us an event")
    assert elapsed / 1000 < 200e-6


# ---- the span on the profiler's clock

def test_init_is_in_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        hvt.shutdown()
        hvt.init()
    finally:
        jax.profiler.stop_trace()
        hvt.init()
    written, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)
    host = next(p for p in ProfileData.from_file(written).planes
                if p.name == "/host:CPU")
    names = {e.name for line in host.lines for e in line.events}
    assert {"hvt_startup/init", "hvt_startup/devices"} <= names
