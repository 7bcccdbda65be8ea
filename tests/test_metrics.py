"""horovod_tpu.metrics — registry semantics, exposition formats, the
engine-stats bridge (including over a real 2-process gang), the scrape
endpoints, and the instrumentation overhead bound.

Everything here is deliberately quick (auto-marked via conftest) so the
telemetry plane is validated by ``ci.sh --fast`` — observability is the
harness's own eye on the data plane, so it must be covered by the inner
loop, not just the round gate."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

from horovod_tpu.metrics.registry import (DEFAULT_LATENCY_BUCKETS,
                                          MetricError, MetricRegistry)
from horovod_tpu.metrics import exposition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build", "libhvt_core.so")


# ------------------------------------------------------------------ registry

def test_counter_inc_and_negative_rejected():
    reg = MetricRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(MetricError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    reg = MetricRegistry()
    g = reg.gauge("g", "help")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == 13


def test_labels_distinct_children_and_validation():
    reg = MetricRegistry()
    c = reg.counter("req_total", "help", ("op", "process_set"))
    c.labels(op="allreduce", process_set="global").inc(3)
    c.labels("allreduce", "0,1").inc(1)
    assert c.labels(op="allreduce", process_set="global").value == 3
    assert c.labels(op="allreduce", process_set="0,1").value == 1
    with pytest.raises(MetricError):
        c.labels(op="allreduce")  # missing label
    with pytest.raises(MetricError):
        c.labels(op="allreduce", process_set="global", extra="x")
    with pytest.raises(MetricError):
        c.inc()  # labeled metric needs .labels(...)


def test_registry_get_or_create_and_schema_conflict():
    reg = MetricRegistry()
    a = reg.counter("x_total", "help")
    assert reg.counter("x_total") is a
    with pytest.raises(MetricError):
        reg.gauge("x_total")
    with pytest.raises(MetricError):
        reg.counter("x_total", labelnames=("op",))
    with pytest.raises(MetricError):
        reg.counter("9bad")  # leading digit
    with pytest.raises(MetricError):
        reg.counter("bad-name")  # invalid char


def test_histogram_bucket_assignment():
    reg = MetricRegistry()
    h = reg.histogram("lat_seconds", "help", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    cum, s, c = h.labels().snapshot()
    # cumulative per the Prometheus convention: le=0.1 → 1, le=1 → 3,
    # le=10 → 4, +Inf → 5
    assert cum == [1, 3, 4, 5]
    assert c == 5
    assert s == pytest.approx(56.05)


def test_default_latency_buckets_are_log_scale():
    bs = DEFAULT_LATENCY_BUCKETS
    assert bs[0] == pytest.approx(1e-6)
    ratios = {round(b2 / b1, 6) for b1, b2 in zip(bs, bs[1:])}
    assert ratios == {4.0}
    assert bs[-1] > 60  # spans loopback-eager to behind-a-stall


def test_concurrent_increments_are_exact():
    reg = MetricRegistry()
    c = reg.counter("n_total", "help")
    h = reg.histogram("h_seconds", "help")
    n_threads, per_thread = 8, 2000

    def hammer():
        for _ in range(per_thread):
            c.inc()
            h.observe(1e-5)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread
    _, _, count = h.labels().snapshot()
    assert count == n_threads * per_thread


# ---------------------------------------------------------------- exposition

def _golden_registry():
    reg = MetricRegistry()
    c = reg.counter("hvt_demo_total", "demo counter", ("op",))
    c.labels(op="allreduce").inc(3)
    g = reg.gauge("hvt_demo_gauge", 'help with "quotes" and \\slash')
    g.set(2.5)
    h = reg.histogram("hvt_demo_seconds", "demo latency",
                      buckets=(0.001, 1.0))
    h.observe(0.0009765625)  # 2^-10: exact in binary → stable golden sum
    h.observe(0.5)
    h.observe(2.0)
    return reg


def test_prometheus_text_golden():
    text = exposition.prometheus_text(_golden_registry())
    assert text == textwrap.dedent("""\
        # HELP hvt_demo_total demo counter
        # TYPE hvt_demo_total counter
        hvt_demo_total{op="allreduce"} 3
        # HELP hvt_demo_gauge help with "quotes" and \\\\slash
        # TYPE hvt_demo_gauge gauge
        hvt_demo_gauge 2.5
        # HELP hvt_demo_seconds demo latency
        # TYPE hvt_demo_seconds histogram
        hvt_demo_seconds_bucket{le="0.001"} 1
        hvt_demo_seconds_bucket{le="1"} 2
        hvt_demo_seconds_bucket{le="+Inf"} 3
        hvt_demo_seconds_sum 2.5009765625
        hvt_demo_seconds_count 3
        """)


def test_json_snapshot_golden():
    snap = exposition.json_snapshot(_golden_registry())
    assert snap["hvt_demo_total"]["type"] == "counter"
    assert snap["hvt_demo_total"]["samples"] == [
        {"labels": {"op": "allreduce"}, "value": 3.0}]
    hist = snap["hvt_demo_seconds"]["samples"][0]
    assert hist["buckets"] == {"0.001": 1, "1": 2, "+Inf": 3}
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(2.5009765625)
    json.dumps(snap)  # must be JSON-serializable as-is


def test_label_value_escaping():
    reg = MetricRegistry()
    reg.counter("e_total", "h", ("k",)).labels(k='a"b\\c\nd').inc()
    text = exposition.prometheus_text(reg)
    assert r'e_total{k="a\"b\\c\nd"} 1' in text


# -------------------------------------------------------------- engine bridge

def test_default_registry_emits_engine_series_without_engine():
    """The hvt_engine_* series must exist (zeros) even when no engine is
    running — BENCH records and dashboards need a stable schema."""
    from horovod_tpu import metrics

    text = metrics.prometheus_text()
    assert "hvt_engine_cycles_total 0" in text
    assert "hvt_cache_hits_total 0" in text
    assert 'hvt_engine_exec_seconds_total{op="allreduce"} 0' in text
    snap = metrics.json_snapshot()
    assert snap["hvt_engine_cycles_total"]["samples"][0]["value"] == 0
    assert snap["hvt_engine_up"]["samples"][0]["value"] == 0


def test_native_engine_stats_layout():
    from horovod_tpu.engine import native

    if not native.available():
        pytest.skip("C++ engine not built")
    stats = native.engine_stats()
    for key in native.STATS_SCALARS:
        assert key in stats
    assert set(stats["exec_ns"]) == set(native.STATS_OPS)
    assert set(stats["exec_count"]) == set(native.STATS_OPS)


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built")
def test_engine_stats_bridge_2proc_gang_and_scrape():
    """Acceptance pin: during a real 2-process CPU-ring run, each worker's
    GET /metrics returns Prometheus text with live engine counters and
    the per-op latency histogram."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    body = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import horovod_tpu as hvt
        hvt.init()
        r = hvt.rank()
        for i in range(8):
            np.testing.assert_allclose(
                np.asarray(hvt.allreduce(
                    np.full((64,), float(r + 1), np.float32),
                    name=f"t{{i}}")),
                1.5)
        from horovod_tpu import metrics
        import re, urllib.request
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{{metrics.server_port()}}/metrics",
            timeout=10).read().decode()
        for needle in (
                "hvt_cache_hits_total",
                'hvt_collective_latency_seconds_bucket{{op="allreduce"'
                ',process_set="global",le="+Inf"}} 8',
                'hvt_engine_exec_seconds_total{{op="allreduce"}}'):
            assert needle in text, text[:3000]
        cyc = float(re.search(
            r"^hvt_engine_cycles_total (\\S+)$", text, re.M).group(1))
        assert cyc > 0
        stats = metrics.json_snapshot()
        assert stats["hvt_engine_up"]["samples"][0]["value"] == 1
        print(f"METRICS-OK-{{r}}", flush=True)
        hvt.shutdown()
    """)
    path = f"/tmp/hvt_metrics_gang_{os.getpid()}.py"
    with open(path, "w") as f:
        f.write(body)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--master-port", str(port), "--metrics-port", "0",
         sys.executable, path],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=90)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    assert "METRICS-OK-0" in out and "METRICS-OK-1" in out


# ------------------------------------------------------------------ endpoints

def test_standalone_serve_routes():
    reg_port = None
    from horovod_tpu.metrics.exposition import MetricsServer

    reg = MetricRegistry()
    reg.counter("served_total", "h").inc(7)
    srv = MetricsServer(reg)
    try:
        reg_port = srv.start(0, addr="127.0.0.1")
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{reg_port}/metrics", timeout=5)
        assert text.headers["Content-Type"].startswith("text/plain")
        assert b"served_total 7" in text.read()
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{reg_port}/metrics.json",
            timeout=5).read())
        assert snap["served_total"]["samples"][0]["value"] == 7
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{reg_port}/nope", timeout=5)
    finally:
        srv.stop()


def test_rendezvous_server_metrics_route():
    """The elastic rendezvous server exposes the same scrape surface."""
    from horovod_tpu.runner.http_server import RendezvousServer

    srv = RendezvousServer()
    port = srv.start()
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "hvt_engine_cycles_total" in text
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
        assert "hvt_engine_cycles_total" in snap
    finally:
        srv.stop()


def test_hvtrun_metrics_port_env_plumbing():
    from horovod_tpu.runner.hosts import get_host_assignments, parse_hosts
    from horovod_tpu.runner.launch import parse_args, slot_env

    args = parse_args(["-np", "1", "--metrics-port", "9090", "true"])
    slots = get_host_assignments(parse_hosts("localhost:1"), 1)
    env = slot_env({}, slots[0], args, "127.0.0.1")
    assert env["HVT_METRICS_PORT"] == "9090"
    # without the flag the env var must be absent (no accidental server)
    args = parse_args(["-np", "1", "true"])
    env = slot_env({}, slots[0], args, "127.0.0.1")
    assert "HVT_METRICS_PORT" not in env


# ------------------------------------------------------------------ callbacks

def test_jax_metrics_callback_publishes_gauges():
    from horovod_tpu.jax.callbacks import MetricsCallback

    reg = MetricRegistry()
    cb = MetricsCallback(registry=reg)
    out = cb.on_epoch_end(0, {"loss": 0.5, "acc": 0.9, "note": "skip-me"})
    assert out == {"loss": 0.5, "acc": 0.9, "note": "skip-me"}
    cb.on_epoch_end(1, {"loss": 0.25})
    g = reg.get("hvt_train_metric")
    assert g.labels(metric="loss").value == 0.25
    assert g.labels(metric="acc").value == 0.9
    assert reg.get("hvt_train_epochs_total").value == 2


def test_eager_dispatch_instrumentation_single_process():
    """A single-process eager allreduce still lands in the dispatch
    histogram/byte counter (the immediate path is instrumented too)."""
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu import metrics

    hist = metrics.registry().get("hvt_collective_latency_seconds")
    before = 0
    if hist is not None:
        _, _, before = hist.labels(
            op="allreduce", process_set="global").snapshot()
    hvt.allreduce(np.ones(4, np.float32), name="metrics_probe")
    hist = metrics.registry().get("hvt_collective_latency_seconds")
    _, _, after = hist.labels(
        op="allreduce", process_set="global").snapshot()
    assert after == before + 1
    assert metrics.registry().get(
        "hvt_collective_bytes_total").labels(
            op="allreduce", process_set="global").value >= 16


# ------------------------------------------------------------------- overhead

def test_observe_overhead_bound():
    """Acceptance: registry overhead < 2% of step time. The CPU bench
    step is ≥ 10 ms and each step does ONE dispatch observation, so the
    per-observe budget is 200 µs; require 20 µs mean (10x margin) to
    keep the bound meaningful and non-flaky on a loaded 1-core host."""
    reg = MetricRegistry()
    h = reg.histogram("bench_seconds", "h", ("op", "process_set"))
    c = reg.counter("bench_total", "h", ("op", "process_set"))
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        h.labels(op="allreduce", process_set="global").observe(1e-4)
        c.labels(op="allreduce", process_set="global").inc(1024)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"observe+inc cost {per_call * 1e6:.1f} µs"


# ------------------------------------------- exposition conformance (PR 13)
# The fleet telemetry plane ships merged host frames to external scrape
# agents, so the text format must stay strictly Prometheus-0.0.4
# conformant: HELP/TYPE per family, ascending `le` bounds with +Inf
# last, cumulative bucket counts, escaped label values.

def _conformance_registry():
    reg = MetricRegistry()
    reg.counter("conf_total", "a counter", ("op",)).labels(
        op="allreduce").inc(2)
    h = reg.histogram("conf_seconds", "a histogram")
    for v in (1e-6, 1e-3, 5.0, 1e9):
        h.observe(v)
    reg.gauge("conf_gauge", "line1\nline2").set(1)
    return reg


def test_exposition_help_and_type_for_every_family():
    reg = _conformance_registry()
    text = exposition.prometheus_text(reg)
    for name, mtype in (("conf_total", "counter"),
                        ("conf_seconds", "histogram"),
                        ("conf_gauge", "gauge")):
        assert f"# TYPE {name} {mtype}" in text
        assert f"# HELP {name} " in text
        # HELP must precede TYPE which must precede the samples
        assert text.index(f"# HELP {name}") < text.index(
            f"# TYPE {name}") < text.index(f"\n{name}")
    # newlines in help text are escaped, never literal
    assert r"line1\nline2" in text and "line1\nline2" not in text


def test_exposition_bucket_ordering_and_inf():
    text = exposition.prometheus_text(_conformance_registry())
    bounds, counts = [], []
    for line in text.splitlines():
        if line.startswith("conf_seconds_bucket"):
            le = line.split('le="', 1)[1].split('"', 1)[0]
            bounds.append(le)
            counts.append(int(line.rsplit(" ", 1)[1]))
    assert bounds[-1] == "+Inf"
    finite = [float(b) for b in bounds[:-1]]
    assert finite == sorted(finite), "le bounds must ascend"
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert counts[-1] == 4  # +Inf covers every observation
    # sum/count close the series
    assert "conf_seconds_sum" in text and "conf_seconds_count 4" in text


def test_exposition_label_escaping_roundtrip_chars():
    reg = MetricRegistry()
    reg.counter("esc_total", "h", ("k",)).labels(
        k='q"uote\\slash\nnl').inc()
    text = exposition.prometheus_text(reg)
    assert 'esc_total{k="q\\"uote\\\\slash\\nnl"} 1' in text


# ------------------------------------------------------ merge algebra (PR 13)

def _frame_of(rank, ctrl=0, depth=0, lat=()):
    """A rank's snapshot frame via the real json_snapshot path — merge
    is pinned against the exposition format, not a hand-rolled dict."""
    from horovod_tpu.metrics import merge as M

    reg = MetricRegistry()
    reg.counter("m_ctrl_total", "c").inc(ctrl)
    reg.gauge("m_depth", "g", ("lane",)).labels(lane="0").set(depth)
    h = reg.histogram("m_lat_seconds", "h", buckets=(0.001, 1.0))
    for v in lat:
        h.observe(v)
    return M.frame(rank, exposition.json_snapshot(reg))


def test_merge_semantics_per_type():
    from horovod_tpu.metrics import merge as M

    a = _frame_of(0, ctrl=100, depth=3, lat=(0.0005,))
    b = _frame_of(1, ctrl=40, depth=7, lat=(0.5, 2.0))
    m = M.merge(a, b)
    assert m["ranks"] == [0, 1]
    assert M.counter_total(m, "m_ctrl_total") == 140  # counters sum
    depth = m["metrics"]["m_depth"]["samples"][0]
    assert depth["labels"] == {"lane": "0"} and depth["value"] == 7
    hist = m["metrics"]["m_lat_seconds"]["samples"][0]
    # bucket-wise ADD of the (cumulative) per-rank snapshots
    assert hist["buckets"] == {"0.001": 1, "1": 2, "+Inf": 3}
    assert hist["count"] == 3


def test_merge_histogram_associativity():
    # binary-exact observation values: float addition is associative
    # only up to rounding, and the pin is about the ALGEBRA (bucket
    # unions, sample keying), not about fp arithmetic
    from horovod_tpu.metrics import merge as M

    a = _frame_of(0, ctrl=1, lat=(0.0009765625, 0.5))
    b = _frame_of(1, ctrl=2, lat=(2.0,))
    c = _frame_of(2, ctrl=4, lat=(0.5, 0.5, 8.0))
    assert M.merge(a, M.merge(b, c)) == M.merge(M.merge(a, b), c)
    # and commutative
    assert M.merge(a, b) == M.merge(b, a)


def test_merge_histogram_layout_mismatch_raises():
    # snapshot buckets are CUMULATIVE: unioning different bound sets
    # would credit counts to the wrong bounds (le=1 missing an
    # observation at 0.5 counted only under a coarser layout) — a
    # layout mismatch must refuse, like a type mismatch
    from horovod_tpu.metrics import merge as M

    def hist_frame(rank, bounds, obs):
        reg = MetricRegistry()
        h = reg.histogram("hm_seconds", "h", buckets=bounds)
        for v in obs:
            h.observe(v)
        return M.frame(rank, exposition.json_snapshot(reg))

    a = hist_frame(0, (0.001, 1.0), (0.5,))
    b = hist_frame(1, (1.0,), (0.5,))
    with pytest.raises(MetricError):
        M.merge(a, b)
    # identical layouts still fold
    c = hist_frame(2, (0.001, 1.0), (2.0,))
    assert M.merge(a, c)["metrics"]["hm_seconds"]["samples"][0][
        "buckets"] == {"0.001": 0, "1": 1, "+Inf": 2}


def test_merge_type_conflict_raises():
    from horovod_tpu.metrics import merge as M

    a = M.frame(0, {"x": {"type": "counter", "help": "",
                          "samples": [{"labels": {}, "value": 1}]}})
    b = M.frame(1, {"x": {"type": "gauge", "help": "",
                          "samples": [{"labels": {}, "value": 1}]}})
    with pytest.raises(MetricError):
        M.merge(a, b)


def test_merge_does_not_mutate_inputs():
    from horovod_tpu.metrics import merge as M

    a = _frame_of(0, ctrl=5, lat=(0.5,))
    b = _frame_of(1, ctrl=7, lat=(0.5,))
    a_before = json.dumps(a, sort_keys=True)
    M.merge(a, b)
    assert json.dumps(a, sort_keys=True) == a_before
