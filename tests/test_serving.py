"""Serving-gang subsystem tests (ISSUE 6).

Three layers:

- **units** (no engine): replica partitioning, deterministic
  shed-on-backlog window accounting, loadtest artifact schema, the
  shared waiter pool (no thread churn), autoscaler policy decisions;
- **driver integration** (fake workers, real ElasticDriver +
  RendezvousServer): scale-out on sustained backlog via zero-downtime
  re-rendezvous, shed-and-blacklist on a failure report naming a killed
  rank;
- **gangs** (real multi-process engines on loopback): two disjoint
  2-rank sets with independent allreduce streams — bit-exact per set,
  per-set cache lanes engaged, mixed set+global traffic in one cycle,
  lane isolation under saturation — and a loopback ReplicaGang loadgen
  replay producing a schema-valid artifact with aligned shed counts.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from horovod_tpu.runner.elastic.autoscaler import (Autoscaler,
                                                   AutoscalePolicy,
                                                   maybe_start_autoscaler)
from horovod_tpu.serving import loadgen
from horovod_tpu.serving.replica_gang import partition_replicas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build", "libhvt_core.so")

_PORT = [26000 + (os.getpid() * 389) % 9000]


def _next_port():
    import socket
    while True:
        _PORT[0] += 1
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", _PORT[0]))
                return _PORT[0]
            except OSError:
                continue


def run_workers(body, np_=4, timeout=180, extra_env=None):
    _next_port()
    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import horovod_tpu as hvt
        hvt.init()
        r, n = hvt.rank(), hvt.size()
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print(f"WORKER-{{r}}-DONE", flush=True)
        hvt.shutdown()
    """)
    path = f"/tmp/hvt_servtest_{os.getpid()}_{_PORT[0]}.py"
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
         str(np_), "--master-port", str(_PORT[0]), sys.executable, path],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"rc={proc.returncode}\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    out = proc.stdout + proc.stderr
    for i in range(np_):
        assert f"WORKER-{i}-DONE" in out
    return out


# ----------------------------------------------------------------- units

def test_partition_replicas():
    assert partition_replicas(4, 2) == [[0, 1], [2, 3]]
    assert partition_replicas(5, 2) == [[0, 1, 2], [3, 4]]
    assert partition_replicas(4, 4) == [[0], [1], [2], [3]]
    assert partition_replicas(3, 1) == [[0, 1, 2]]
    with pytest.raises(ValueError):
        partition_replicas(2, 3)
    with pytest.raises(ValueError):
        partition_replicas(2, 0)


def test_replica_gang_shed_is_deterministic_single_proc():
    """Shed decisions depend only on the aligned submit/reap history:
    with the window full, every further submit sheds — no timing enters
    the decision (the property that keeps replica members aligned)."""
    from horovod_tpu.serving import ReplicaGang

    gang = ReplicaGang(1, admission_timeout=0.5, max_backlog=4)
    x = np.ones(8, np.float32)
    handles = [gang.submit_request(x) for _ in range(10)]
    assert [h is not None for h in handles] == [True] * 4 + [False] * 6
    assert gang.stats.admitted == 4 and gang.stats.shed == 6
    assert gang.backlog() == 4
    # reaping frees the window; admission resumes at exactly that point
    assert gang.reap() is not None
    assert gang.submit_request(x) is not None
    gang.drain()
    assert gang.backlog() == 0
    snap = gang.snapshot()
    assert snap["completed"] == 5 and snap["deadline_miss"] == 0
    assert snap["p99_ms"] >= 0

    # the admission deadline runs from SUBMIT time: with a zero budget
    # every reap is a miss even though the handles complete instantly
    strict = ReplicaGang(1, admission_timeout=0.0, max_backlog=4)
    for _ in range(3):
        strict.submit_request(x)
    strict.drain()
    assert strict.stats.deadline_miss == 3


class _RecordingEngine:
    """Single-rank engine seam stub recording every submission name/op;
    handles complete instantly with the input (world of one)."""

    def __init__(self):
        self.submits = []  # (name, n_tensors, op)

    def rank(self):
        return 0

    def size(self):
        return 1

    def submit(self, name, tensor, members, op="sum"):
        self.submits.append((name, 1, op))
        return [tensor]

    def submit_batch(self, name, tensors, members, op="sum"):
        self.submits.append((name, len(tensors), op))
        return list(tensors)

    def wait(self, handle, timeout=None):
        return handle if len(handle) > 1 else handle[0]


def test_batch_op_change_closes_the_open_batch():
    """A fused batch submission carries ONE reduce op: a request with a
    different op must close the open batch first (an aligned-history
    boundary, so members stay in lockstep) instead of silently riding
    the first request's op."""
    from horovod_tpu.serving import ReplicaGang

    eng = _RecordingEngine()
    gang = ReplicaGang(1, admission_timeout=1.0, max_backlog=8,
                       batch_window=4, engine=eng)
    x = np.ones(8, np.float32)
    gang.submit_request(x, op="sum")
    gang.submit_request(x, op="sum")
    gang.submit_request(x, op="avg")   # boundary: flushes the 2 sums
    gang.submit_request(x, op="avg")
    gang.drain()
    assert [(n, o) for _, n, o in eng.submits] == [(2, "sum"), (2, "avg")]
    assert [d for d in gang.decisions if d[0] == "batch"] == \
        [("batch", 0, 2), ("batch", 2, 2)]


def test_opname_maps_reduce_ops_and_rejects_unknown():
    """collective_ops ReduceOp INSTANCES (no __name__) must map by
    their .name — Average silently coerced to "sum" would inflate
    results by the lane size — and an op the seam cannot express
    raises instead of riding as sum."""
    from horovod_tpu.ops import collective_ops as co
    from horovod_tpu.serving import ReplicaGang

    gang = ReplicaGang(1, admission_timeout=1.0, max_backlog=4,
                       engine=_RecordingEngine())
    assert gang._opname(None) == "sum"
    assert gang._opname("avg") == "avg"
    assert gang._opname(co.Average) == "avg"
    assert gang._opname(co.Sum) == "sum"
    assert gang._opname(co.Min) == "min"
    assert gang._opname(co.Max) == "max"
    assert gang._opname(co.Product) == "prod"
    with pytest.raises(ValueError, match="unsupported"):
        gang._opname("xor")


def test_batched_reap_slo_is_per_request():
    """One slot-level wait timeout (the OLDEST request's blown budget)
    must not mark batch-mates admitted later — whose own latency sits
    inside the deadline — as misses too."""
    from horovod_tpu.serving import ReplicaGang

    gang = ReplicaGang(1, admission_timeout=0.05, max_backlog=8,
                       batch_window=2, engine=_RecordingEngine())
    x = np.ones(4, np.float32)
    gang.submit_request(x)
    time.sleep(0.08)             # first request blows its own budget
    gang.submit_request(x)       # second flushes the batch, fresh clock
    gang.drain()
    assert gang.stats.completed == 2
    assert gang.stats.deadline_miss == 1, gang.stats.deadline_miss


def test_batch_slot_names_unique_across_partial_flush_window():
    """Partial flushes (explicit flush()/op changes) can put up to
    max_backlog single-request slots in flight at once — batch names
    must not cycle back onto a slot that is still pending. Regression:
    the cycle was 2*ceil(backlog/window), which collided from the
    (2*ceil+1)th unreaped partial flush on."""
    from horovod_tpu.serving import ReplicaGang

    eng = _RecordingEngine()
    gang = ReplicaGang(1, admission_timeout=1.0, max_backlog=8,
                       batch_window=8, engine=eng)
    x = np.ones(4, np.float32)
    for _ in range(gang.max_backlog):   # fill the window, never reap
        gang.submit_request(x)
        gang.flush()
    names = [n for n, _, _ in eng.submits]
    assert len(names) == gang.max_backlog
    assert len(set(names)) == len(names), names


def test_replica_stats_reservoir_keeps_tracking_after_cap():
    """The latency reservoir must keep sampling the whole stream once
    full — a frozen early-life p99 would blind the SLO signal the
    autoscaler scales on."""
    from horovod_tpu.serving.replica_gang import ReplicaStats

    st = ReplicaStats(max_samples=64)
    for _ in range(200):
        st.observe(1.0, True)
    assert st.percentile(99) == pytest.approx(1.0)
    for _ in range(2000):
        st.observe(9.0, True)
    # ~94% of the stream is 9.0 by now; a frozen reservoir would still
    # report 1.0
    assert st.percentile(50) == pytest.approx(9.0)
    assert st.completed == 2200


def test_loadgen_artifact_schema_roundtrip(tmp_path):
    snaps = {
        "baseline": [
            {"rank": r, "replica": r // 2, "admitted": 10, "shed": 2,
             "completed": 10, "deadline_miss": 1, "p50_ms": 1.0,
             "p99_ms": 2.0, "throughput_rps": 5.0} for r in range(4)],
        "contended": [
            {"rank": r, "replica": r // 2, "admitted": 10, "shed": 4,
             "completed": 10, "deadline_miss": 2, "p50_ms": 1.1,
             "p99_ms": 2.2, "throughput_rps": 5.0} for r in range(4)],
    }
    config = {"saturate_replica": 0}
    doc = loadgen.build_artifact(config, snaps)
    assert loadgen.validate_artifact(doc) == []
    iso = doc["isolation"]
    assert iso["observed_replica"] == 1
    assert iso["ratio"] == pytest.approx(2.2 / 2.0, rel=1e-3)
    # --check CLI path
    p = tmp_path / "art.json"
    p.write_text(json.dumps(doc))
    assert loadgen.main(["--check", str(p)]) == 0
    bad = dict(doc)
    bad.pop("phases")
    p.write_text(json.dumps(bad))
    assert loadgen.main(["--check", str(p)]) == 1


def test_combine_handles_waiter_pool_no_thread_growth():
    """Grouped calls must not spawn a thread per call (satellite): the
    shared waiter pool scales with peak CONCURRENCY (bounded), never
    with call count, and reuses its threads across waves."""
    from horovod_tpu.engine import api

    combos = []
    for i in range(50):
        hs = [api.Handle() for _ in range(3)]
        combos.append((i, hs, api._combine_handles(hs)))
        for h in hs:
            h._set_result(i)
    for i, hs, c in combos:
        assert c.wait(timeout=10) == [i, i, i]
    waiters = [t for t in threading.enumerate()
               if t.name == "hvt-waiter"]
    # 50 sequential calls: far fewer threads than calls, under the cap
    assert 0 < len(waiters) <= api._waiters._max_threads
    assert len(waiters) < 25
    # a second sequential wave reuses the pool — no per-call growth
    for i in range(50):
        hs = [api.Handle() for _ in range(2)]
        c = api._combine_handles(hs)
        for h in hs:
            h._set_result(i)
        assert c.wait(timeout=10) == [i, i]
    after = [t for t in threading.enumerate() if t.name == "hvt-waiter"]
    assert len(after) <= len(waiters) + 2


def test_combine_handles_no_head_of_line_blocking():
    """A stalled lane's grouped waits must not freeze an unrelated
    group's completion: the pool grows with outstanding jobs, so a
    fast group resolves while several slow ones are still blocked."""
    from horovod_tpu.engine import api

    slow = [[api.Handle() for _ in range(2)] for _ in range(6)]
    slow_combined = [api._combine_handles(hs) for hs in slow]
    fast = [api.Handle(), api.Handle()]
    fast_combined = api._combine_handles(fast)
    for h in fast:
        h._set_result(7)
    # the fast group resolves while all six slow groups stay blocked
    assert fast_combined.wait(timeout=10) == [7, 7]
    assert not any(c.done() for c in slow_combined)
    for hs in slow:
        for h in hs:
            h._set_result(0)
    for c in slow_combined:
        assert c.wait(timeout=10) == [0, 0]


# ------------------------------------------------------- autoscaler units

class FakeStore:
    def __init__(self):
        self._scopes = {}

    def put(self, scope, key, value):
        self._scopes.setdefault(scope, {})[key] = value

    def get(self, scope, key):
        return self._scopes.get(scope, {}).get(key)

    def keys(self, scope):
        return list(self._scopes.get(scope, {}))


class FakeDriver:
    def __init__(self, world=2, avail=4, hosts=None):
        self._world = world
        self._avail = avail
        self.notifications = 0
        self.host_manager = SimpleNamespace(
            current_hosts=SimpleNamespace(
                count_available_slots=lambda: self._avail),
            blacklist=lambda host: self.blacklisted.append(host))
        self.blacklisted = []
        self.failure_reports = []
        self._assignments = {
            (h, s): SimpleNamespace(rank=r, hostname=h)
            for r, (h, s) in enumerate(hosts or [("a", 0), ("b", 0)])}
        self._lock = threading.Lock()

    def world_size(self):
        return self._world

    def _notify_workers_host_changes(self):
        self.notifications += 1

    def _on_failure_report(self, key, value):
        self.failure_reports.append((key, value))

    def finished(self):
        return False


def _scaler(driver, store=None, **policy):
    rdv = SimpleNamespace(store=store or FakeStore())
    defaults = dict(backlog_threshold=8, sustain_sec=5,
                    cooldown_sec=100, interval_sec=1)
    defaults.update(policy)
    return Autoscaler(driver, rdv, AutoscalePolicy(**defaults)), rdv.store


def test_autoscaler_scale_out_needs_sustained_backlog_and_cooldown():
    drv = FakeDriver(world=2, avail=4)
    scaler, store = _scaler(drv)
    store.put("serving", "1", json.dumps({"inflight": 12}).encode())
    scaler.step(now=100.0)
    scaler.step(now=103.0)
    assert drv.notifications == 0          # not sustained yet
    scaler.step(now=106.0)
    assert drv.notifications == 1          # sustained ≥ 5 s → scale out
    assert [a for _, a, _ in scaler.decisions] == ["scale_out"]
    scaler.step(now=108.0)
    scaler.step(now=120.0)
    assert drv.notifications == 1          # cooldown holds


def test_autoscaler_backlog_clears_resets_sustain_window():
    drv = FakeDriver(world=2, avail=4)
    scaler, store = _scaler(drv, sustain_sec=4)
    store.put("serving", "0", json.dumps({"inflight": 9}).encode())
    scaler.step(now=0.0)
    store.put("serving", "0", json.dumps({"inflight": 0}).encode())
    scaler.step(now=3.0)                   # backlog gone → window resets
    store.put("serving", "0", json.dumps({"inflight": 9}).encode())
    scaler.step(now=5.0)
    scaler.step(now=8.0)                   # only 3 s sustained
    assert drv.notifications == 0


def test_autoscaler_no_scale_out_without_spare_slots():
    drv = FakeDriver(world=4, avail=4)
    scaler, store = _scaler(drv, sustain_sec=0, cooldown_sec=0)
    store.put("serving", "2", json.dumps({"inflight": 99}).encode())
    for t in range(5):
        scaler.step(now=float(t))
    assert drv.notifications == 0
    assert scaler.decisions == []


def test_autoscaler_reads_engine_queue_depth_from_debugz():
    drv = FakeDriver()
    scaler, store = _scaler(drv)
    store.put("debugz", "1",
              json.dumps({"engine": {"queue_depth": 11}}).encode())
    store.put("serving", "1", json.dumps({"inflight": 2}).encode())
    assert scaler.read_backlog() == 11


def test_autoscaler_ignores_stale_and_out_of_world_snapshots():
    """The serving/debugz scopes survive round resets by design, so a
    shed rank's final push must not drive scale decisions forever: a
    payload that stops CHANGING goes stale on the driver's monotonic
    clock (no cross-host wall clocks involved), and rank ids beyond the
    current world are discarded outright."""
    drv = FakeDriver(world=2)
    scaler, store = _scaler(drv)
    store.put("serving", "1", json.dumps({"inflight": 64}).encode())
    store.put("debugz", "0",
              json.dumps({"engine": {"queue_depth": 40}}).encode())
    # rank ids from a bigger previous round → ignored regardless of age
    store.put("serving", "5", json.dumps({"inflight": 99}).encode())
    store.put("debugz", "7",
              json.dumps({"engine": {"queue_depth": 50}}).encode())
    assert scaler.read_backlog(mono_now=100.0) == 64.0
    # unchanged payloads 60 s later = dead ranks → both scopes age out
    assert scaler.read_backlog(mono_now=160.0) == 0.0
    # a changed (live) payload is fresh again
    store.put("serving", "1", json.dumps({"inflight": 12}).encode())
    assert scaler.read_backlog(mono_now=161.0) == 12.0


def test_autoscaler_failed_notify_keeps_sustain_window_armed():
    """A transient notify failure must not consume the sustain window:
    the scale-out retries on the next step, and no decision is recorded
    until the notification actually went out."""
    drv = FakeDriver(world=2, avail=4)

    calls = {"n": 0}

    def flaky_notify():
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("worker endpoint down")

    drv._notify_workers_host_changes = flaky_notify
    scaler, store = _scaler(drv, sustain_sec=0, cooldown_sec=0)
    store.put("serving", "0", json.dumps({"inflight": 30}).encode())
    scaler.step(now=1.0)
    assert calls["n"] == 1 and scaler.decisions == []
    scaler.step(now=2.0)       # retried immediately, now succeeds
    assert calls["n"] == 2
    assert [a for _, a, _ in scaler.decisions] == ["scale_out"]


def test_autoscaler_shed_delegates_to_driver_failure_handler():
    """Shed routes every unseen failure report through the driver's own
    ``_on_failure_report`` — one home for the blacklist policy (the
    guard semantics themselves are pinned by the driver's tests and the
    real-driver integration test below) — exactly once per report."""
    drv = FakeDriver(hosts=[("a", 0), ("b", 0)])
    scaler, store = _scaler(drv)
    report = json.dumps({"failed_ranks": [1], "error": "peer_lost: "
                         "control connection to rank 1 lost"}).encode()
    store.put("failure", "a/0", report)
    scaler.step(now=0.0)
    assert drv.failure_reports == [("a/0", report)]
    assert [a for _, a, _ in scaler.decisions] == ["shed"]
    scaler.step(now=1.0)                   # report already seen
    assert len(scaler.decisions) == 1
    assert len(drv.failure_reports) == 1
    # a LATER ROUND's genuinely-new report may reuse the key (the
    # failure scope is cleared at round resets) — dedup is by payload
    report2 = json.dumps({"failed_ranks": [0], "error": "x"}).encode()
    store.put("failure", "a/0", report2)
    scaler.step(now=2.0)
    assert drv.failure_reports[-1] == ("a/0", report2)
    assert len(scaler.decisions) == 2


def test_autoscaler_spare_slots_capped_by_max_np():
    """Slots beyond the driver's max_np are not scalable capacity — a
    'scale-out' onto them would re-rendezvous the gang for an unchanged
    world, every cooldown, forever."""
    drv = FakeDriver(world=4, avail=6)
    drv._settings = SimpleNamespace(max_np=4)
    scaler, store = _scaler(drv, sustain_sec=0, cooldown_sec=0)
    store.put("serving", "0", json.dumps({"inflight": 99}).encode())
    assert scaler.spare_slots() == 0
    scaler.step(now=1.0)
    assert drv.notifications == 0 and scaler.decisions == []
    drv._settings = SimpleNamespace(max_np=6)
    assert scaler.spare_slots() == 2


def test_autoscaler_survives_non_dict_kv_payloads():
    """Valid-JSON-but-not-an-object KV payloads (buggy/old pushers,
    manual curl) must be skipped, not abort every step() forever —
    the serving scope is kept across rounds, so a poison key would
    otherwise disable the autoscaler until launcher restart."""
    drv = FakeDriver(world=4, avail=4)
    scaler, store = _scaler(drv)
    store.put("serving", "0", b"[1, 2, 3]")
    store.put("debugz", "1", b"\"a string\"")
    store.put("failure", "a/0", b"42")
    store.put("serving", "1", json.dumps({"inflight": 7}).encode())
    assert scaler.read_backlog(mono_now=1.0) == 7.0
    assert scaler.read_failed_ranks() == {}
    assert scaler.read_failed_ranks() == {}  # bad key seen once, skipped
    scaler.step(now=0.0)                     # whole step stays alive


def test_autoscaler_scale_out_waits_for_notify_endpoints():
    """The driver's notify is a silent no-op with no registered worker
    endpoints; the autoscaler must not burn the sustain window +
    cooldown on a notification nobody heard."""
    drv = FakeDriver(world=2, avail=4)
    drv._worker_notify_addrs = lambda: []
    scaler, store = _scaler(drv, sustain_sec=0, cooldown_sec=0)
    store.put("serving", "0", json.dumps({"inflight": 30}).encode())
    scaler.step(now=1.0)
    assert drv.notifications == 0 and scaler.decisions == []
    drv._worker_notify_addrs = lambda: ["127.0.0.1:1"]
    scaler.step(now=2.0)                     # retries next poll
    assert drv.notifications == 1
    assert [a for _, a, _ in scaler.decisions] == ["scale_out"]


def test_maybe_start_autoscaler_env_gated(monkeypatch):
    drv = FakeDriver()
    rdv = SimpleNamespace(store=FakeStore())
    monkeypatch.delenv("HVT_AUTOSCALE", raising=False)
    assert maybe_start_autoscaler(drv, rdv) is None
    monkeypatch.setenv("HVT_AUTOSCALE", "1")
    scaler = maybe_start_autoscaler(drv, rdv)
    assert scaler is not None
    scaler.stop()


# ------------------------------------------- real-driver integration

def _wait_until(cond, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def _make_driver(discovery, min_np, max_np, worker_fn):
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.settings import ElasticSettings
    from horovod_tpu.runner.http_server import RendezvousServer

    settings = ElasticSettings(min_np=min_np, max_np=max_np,
                               elastic_timeout=5.0,
                               discovery_interval=0.01)
    rendezvous = RendezvousServer()
    driver = ElasticDriver(rendezvous, discovery, settings,
                           create_worker_fn=worker_fn)
    return driver, rendezvous


def test_autoscaler_scale_out_grows_world_with_real_driver():
    """Backlog → notify → workers re-rendezvous → the next round runs
    on every available slot: the zero-downtime scale-out path."""
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.elastic.notification import \
        WorkerNotificationManager

    sizes = []
    release = threading.Event()
    updates = []

    class RecordingState:
        def on_hosts_updated(self, ts, res):
            updates.append((ts, res))

    def worker(slot):
        sizes.append((slot.rank, slot.size))
        if slot.size == 1:
            # round 1: the lone serving worker waits for the
            # autoscaler's host-update notification, then reports READY
            # (the elastic @run wrapper's commit-point behavior)
            if not _wait_until(lambda: updates, timeout=8):
                return 1
            driver.record_ready(slot.hostname, slot.local_rank)
            release.wait(8)
        else:
            release.set()
        return 0

    driver, rendezvous = _make_driver(FixedHostDiscovery({"host-1": 2}),
                                      min_np=1, max_np=2,
                                      worker_fn=worker)
    rendezvous.start()
    mgr = WorkerNotificationManager()
    mgr.start_server()
    mgr.register_state(RecordingState())
    rendezvous.store.put(
        "workers", "0",
        json.dumps({"host": "127.0.0.1", "port": mgr.port}).encode())
    # heavy serving backlog reported by the worker
    rendezvous.store.put("serving", "0",
                         json.dumps({"inflight": 64}).encode())
    scaler = Autoscaler(driver, rendezvous,
                        AutoscalePolicy(backlog_threshold=8,
                                        sustain_sec=0, cooldown_sec=0,
                                        interval_sec=0.05))
    try:
        driver.start(1)
        assert driver.world_size() == 1
        assert scaler.spare_slots() == 1
        scaler.step(now=1.0)
        assert [a for _, a, _ in scaler.decisions] == ["scale_out"]
        assert driver.wait(15)
        assert driver.error is None, driver.error
        assert driver.world_size() == 2      # scaled onto the spare slot
        assert (0, 1) in sizes and any(s == 2 for _, s in sizes)
    finally:
        driver.stop()
        rendezvous.stop()


def test_autoscaler_shed_and_blacklist_rejoins_without_killed_host():
    """A survivor's failure report (what the elastic @run wrapper PUTs
    after an HVT_FAULT_INJECT kill) sheds the killed rank's host via the
    autoscaler; the barrier's re-rendezvous then runs on the survivors
    with stable ranks and the job finishes clean — zero downtime."""
    rounds = []
    killed_once = threading.Event()

    def worker(slot):
        rounds.append((slot.hostname, slot.local_rank, slot.rank,
                       slot.size))
        if slot.hostname == "host-2" and not killed_once.is_set():
            killed_once.set()
            # stand-in for the SIGKILL the chaos harness raises
            # (HVT_FAULT_INJECT=kill:rank=2:after_ops=N)
            return 137
        return 0

    class SeqDiscovery:
        def find_available_hosts_and_slots(self):
            return {"host-1": 2, "host-2": 1}

    driver, rendezvous = _make_driver(SeqDiscovery(), min_np=2, max_np=3,
                                      worker_fn=worker)
    scaler = Autoscaler(driver, rendezvous,
                        AutoscalePolicy(backlog_threshold=1e9,
                                        sustain_sec=0, cooldown_sec=0))
    try:
        driver.start(3)
        # the survivor's report lands before host-2's exit trickles in
        rendezvous.store.put(
            "failure", "host-1/0",
            json.dumps({"round": 0,
                        "error": "hvt engine aborted (peer_lost: data "
                                 "connection to rank 2 lost)",
                        "failed_ranks": [2]}).encode())
        scaler.step(now=0.0)
        assert [a for _, a, _ in scaler.decisions] == ["shed"]
        assert driver.host_manager.blacklisted_count() >= 1
        assert driver.wait(15)
        assert driver.error is None, driver.error
        r1 = {(h, s): r for h, s, r, _ in rounds[:3]}
        r2 = {(h, s): r for h, s, r, _ in rounds[3:]}
        assert set(r2) == {("host-1", 0), ("host-1", 1)}  # host-2 shed
        for key in r2:
            assert r2[key] == r1[key]                     # ranks stable
    finally:
        driver.stop()
        rendezvous.stop()


# ------------------------------------------------------------- gang tests

needs_engine = pytest.mark.skipif(
    not os.path.exists(LIB),
    reason="C++ engine not built (make -C horovod_tpu/csrc)")


@needs_engine
def test_concurrent_disjoint_sets_4proc():
    """Two disjoint 2-rank sets run independent allreduce streams:
    bit-exact per-set results, per-set cache lanes engaged (steady-state
    hits on set traffic), mixed set+global traffic in one cycle, and a
    saturated set not inflating the idle set's p99 (lane isolation)."""
    out = run_workers("""
        from horovod_tpu.common.process_sets import ProcessSet, add_process_set
        from horovod_tpu.engine import native
        from horovod_tpu.ops import collective_ops as C

        setA = add_process_set(ProcessSet([0, 1]))
        setB = add_process_set(ProcessSet([2, 3]))
        g = r // 2
        mine, other = (setA, setB) if g == 0 else (setB, setA)
        assert mine.included() and not other.included()
        try:
            C.allreduce(np.ones(4, np.float32), op=C.Sum, name="bad",
                        process_set=other)
            raise SystemExit(f"rank {r}: non-member submit did not raise")
        except ValueError:
            pass

        # independent per-set streams, distinct values/shapes per set —
        # results must be bit-exact sums over exactly the set's members
        numel = 96 if g == 0 else 160
        base = np.arange(numel, dtype=np.float32) * (g + 1)
        for k in range(12):
            x = base + np.float32(r % 2 + k)
            res = np.asarray(C.allreduce(x, op=C.Sum, name=f"st.{g}.{k}",
                                         process_set=mine))
            exp = 2 * base + np.float32(0 + k) + np.float32(1 + k)
            np.testing.assert_array_equal(res, exp)

        # steady-state lane cache: the SAME set tensor resubmitted must
        # produce cache hits (set-scoped responses are cacheable now).
        # 2-D on purpose: rank 0 is a NON-member of set B's lane, and
        # its cache copy must carry the true dims (a flattened stand-in
        # would poison the coordinator's hit-fold path)
        hot = np.ones((16, 16), np.float32) * (r + 1)
        for k in range(6):
            res = np.asarray(C.allreduce(hot, op=C.Sum, name=f"hot.{g}",
                                         process_set=mine))
            lo = 2 * g
            np.testing.assert_array_equal(
                res, np.ones((16, 16), np.float32) * ((lo + 1) + (lo + 2)))
        st = native.engine_stats()
        assert st["cache_hits"] > 0, f"rank {r}: no lane cache hits: {st['cache_hits']}"
        assert st["lanes_active"] >= 1, st["lanes_active"]
        assert sum(st["lane_exec_count"]) > 0

        # mixed set+global traffic in one cycle: async set op + global
        # op submitted back-to-back, both land
        ha = C.allreduce_async(np.full(32, np.float32(r % 2 + 1)),
                               op=C.Sum, name=f"mix.{g}",
                               process_set=mine)
        res_g = np.asarray(C.allreduce(np.full(8, np.float32(r + 1)),
                                       op=C.Sum, name="mix.global"))
        np.testing.assert_array_equal(res_g, np.full(8, np.float32(1+2+3+4)))
        np.testing.assert_array_equal(np.asarray(C.synchronize(ha)),
                                      np.full(32, np.float32(3)))

        # lane isolation: set B measures its latency twice — idle gang,
        # then with set A saturating its own lane. One engine thread per
        # PROCESS means B's ranks never execute A's responses; the
        # shared cost is only rank 0's coordination.
        C.barrier()
        def measure(tag, nops=25):
            lat = []
            y = np.ones(64, np.float32)
            for k in range(nops):
                t0 = time.perf_counter()
                C.allreduce(y, op=C.Sum, name=f"p99.{tag}.{k}",
                            process_set=setB)
                lat.append(time.perf_counter() - t0)
            return np.percentile(np.asarray(lat), 99)
        idle_p99 = measure("idle") if g == 1 else None
        C.barrier()
        if g == 0:
            z = np.ones(2048, np.float32)
            for k in range(120):
                C.allreduce(z, op=C.Sum, name=f"sat.{k}", process_set=setA)
        else:
            busy_p99 = measure("busy")
        C.barrier()
        if g == 1 and r == 2:
            ratio = busy_p99 / max(idle_p99, 1e-9)
            print(f"P99-RATIO {ratio:.3f} idle={idle_p99*1e3:.2f}ms "
                  f"busy={busy_p99*1e3:.2f}ms", flush=True)
            # generous CI bound; the committed benchmark artifact pins
            # the 25% isolation claim under controlled load
            assert busy_p99 < max(8 * idle_p99, idle_p99 + 0.25), \
                (idle_p99, busy_p99)
    """, np_=4, timeout=240)
    assert "P99-RATIO" in out


@needs_engine
def test_batching_determinism_under_clock_skew():
    """ISSUE 15 satellite: every replica member computes the identical
    (admitted, shed, batch-boundary) tuple sequence under bursty load
    even when HVT_FAULT_INJECT=delay_ms skews one member's clock — the
    decisions are pure functions of the aligned call history, never of
    timing — and the batched path's results are bit-identical to the
    unbatched path's. Replicas are 2-wide, so fp32 addition is
    commutative-exact and bitwise comparison is safe for arbitrary
    floats."""
    out = run_workers("""
        import zlib
        from horovod_tpu.ops.functions import allgather_object
        from horovod_tpu.serving import ReplicaGang

        rng = np.random.default_rng(7)
        payloads = [rng.standard_normal(192).astype(np.float32)
                    for _ in range(36)]

        def drive(gang):
            outs = []
            k = 0
            # bursty: 7 submits back-to-back (window 5 → sheds), then
            # reap down; the SEQUENCE is identical on every member
            while k < len(payloads):
                for _ in range(min(7, len(payloads) - k)):
                    gang.submit_request(payloads[k])
                    k += 1
                while gang.backlog() > 2:
                    res = gang.reap()
                    outs.extend(res if isinstance(res, list) else [res])
            gang.flush()
            while gang.backlog():
                res = gang.reap()
                outs.extend(res if isinstance(res, list) else [res])
            return outs

        batched = ReplicaGang(2, admission_timeout=2.0, max_backlog=5,
                              batch_window=3, name="bd.b")
        outs_b = drive(batched)
        hvt.barrier()
        unbatched = ReplicaGang(2, admission_timeout=2.0, max_backlog=5,
                                batch_window=1, name="bd.u")
        outs_u = drive(unbatched)
        hvt.barrier()

        # decision tuples member-identical (delay_ms skews rank 1's
        # clock; see extra_env), batch boundaries included
        recs = allgather_object(
            {"rank": r, "replica": batched.replica_id,
             "decisions": list(batched.decisions),
             "admitted": batched.stats.admitted,
             "shed": batched.stats.shed,
             "batches": batched.stats.batches},
            name="bd.gather")
        if r == 0:
            by_rep = {}
            for rec in recs:
                by_rep.setdefault(rec["replica"], []).append(rec)
            for rep, members in by_rep.items():
                base = members[0]
                for mbr in members[1:]:
                    assert mbr["decisions"] == base["decisions"], \
                        (rep, mbr["rank"])
                    assert (mbr["admitted"], mbr["shed"],
                            mbr["batches"]) == (base["admitted"],
                                                base["shed"],
                                                base["batches"])
            assert base["shed"] > 0, "burst 7 > window 5 must shed"
            assert base["batches"] < base["admitted"], \
                "batching must coalesce requests into fewer submissions"
        # bit-identity: batched results == unbatched results, in order
        assert len(outs_b) == len(outs_u) == batched.stats.completed
        crc_b = zlib.crc32(b"".join(np.asarray(o).tobytes()
                                    for o in outs_b))
        crc_u = zlib.crc32(b"".join(np.asarray(o).tobytes()
                                    for o in outs_u))
        assert crc_b == crc_u, (crc_b, crc_u)
        if r == 0:
            print("BATCH-DETERMINISM-OK", flush=True)
    """, np_=4, timeout=240,
        extra_env={"HVT_FAULT_INJECT": "delay_ms:rank=1:20"})
    assert "BATCH-DETERMINISM-OK" in out


def _uring_ok():
    try:
        from horovod_tpu.engine import native
        return native.uring_supported()
    except Exception:
        return False


@needs_engine
@pytest.mark.parametrize("backend", [
    "tcp", pytest.param("io_uring", marks=pytest.mark.skipif(
        not _uring_ok(), reason="io_uring kernel probe failed"))])
def test_lane_pool_parity_and_engagement(backend):
    """HVT_LANE_WORKERS A/B on a real 3-rank gang with two overlapping
    lanes ({0,1} hot, {0,2} idle — they share only rank 0, so the pool
    may run them concurrently): results are bit-identical to the
    single-thread engine, and the pool actually executed tasks (the
    isolation RATIO is pinned by benchmarks/serving_soak.py under
    controlled load, not by this CI box). Parameterized over link
    backends — concurrent lane workers pumping overlapping links is
    the owner-token-claim contract (ProbeAndRepair must SKIP a link
    another thread drives), which each backend's pump must honor."""
    body = """
        import zlib
        from horovod_tpu.common.process_sets import ProcessSet, add_process_set
        from horovod_tpu.engine import native
        from horovod_tpu.ops import collective_ops as C

        laneA = add_process_set(ProcessSet([0, 1]))
        laneB = add_process_set(ProcessSet([0, 2]))
        crc = 0
        for k in range(30):
            hs = []
            if r in (0, 1):
                hs.append(C.allreduce_async(
                    np.full(4096, np.float32(r + 1 + k % 3)), op=C.Sum,
                    name=f"lp.a.{k % 6}", process_set=laneA))
            if r in (0, 2):
                hs.append(C.allreduce_async(
                    np.full(64, np.float32(r + 2)), op=C.Sum,
                    name=f"lp.b.{k % 6}", process_set=laneB))
            for h in hs:
                crc = zlib.crc32(np.asarray(C.synchronize(h)).tobytes(),
                                 crc)
        res = np.asarray(C.allreduce(np.float32([crc % 65521]),
                                     op=C.Sum, name="lp.fin"))
        st = native.engine_stats()
        print(f"LANE-CRC rank={r} crc={crc} pool={st['lane_pool_tasks']}"
              f" workers={st['lane_workers']}", flush=True)
    """
    env0 = {"HVT_LANE_WORKERS": "0", "HVT_SHM_ALLREDUCE": "0",
            "HVT_LINK_BACKEND": backend}
    env2 = {"HVT_LANE_WORKERS": "2", "HVT_SHM_ALLREDUCE": "0",
            "HVT_LINK_BACKEND": backend}
    out0 = run_workers(body, np_=3, timeout=240, extra_env=env0)
    out2 = run_workers(body, np_=3, timeout=240, extra_env=env2)

    def crcs(out):
        found = {}
        for line in out.splitlines():
            if "LANE-CRC" not in line:
                continue  # launcher prefixes "[rank] " to worker lines
            fields = line[line.index("LANE-CRC"):].split()[1:]
            parts = dict(p.split("=") for p in fields)
            found[int(parts["rank"])] = (parts["crc"],
                                         int(parts["pool"]),
                                         int(parts["workers"]))
        return found

    c0, c2 = crcs(out0), crcs(out2)
    assert set(c0) == set(c2) == {0, 1, 2}
    for rank in c0:
        assert c0[rank][0] == c2[rank][0], \
            f"rank {rank}: pool changed results"  # bit-identical
    assert all(v[1] == 0 and v[2] == 0 for v in c0.values())
    assert all(v[2] == 2 for v in c2.values())
    assert c2[0][1] > 0, "pool never engaged on the shared rank"


@needs_engine
def test_replica_gang_loadgen_artifact_4proc(tmp_path):
    """Loopback ReplicaGang replay end to end: artifact schema-valid,
    shed-on-backlog exercised (burst > window) with IDENTICAL admission
    accounting on every member of a replica — the alignment property
    that keeps a shed from wedging the lane."""
    out = run_workers("""
        from horovod_tpu.serving import loadgen as lg
        args = lg._parser().parse_args([
            "--replicas", "2", "--requests", "18", "--bytes", "2048",
            "--burst", "6", "--window", "4", "--admission-ms", "500",
            "--gap-ms", "0.5", "--sync-every", "6",
            "--saturate-factor", "2"])
        doc = lg.run_loadtest(args)
        if r == 0:
            errs = lg.validate_artifact(doc)
            assert errs == [], errs
            assert set(doc["phases"]) == {"baseline", "contended"}
            total_shed = sum(s["shed"]
                             for p in doc["phases"].values()
                             for s in p["ranks"])
            assert total_shed > 0, "burst 6 > window 4 must shed"
            for pname, phase in doc["phases"].items():
                by_rep = {}
                for s in phase["ranks"]:
                    by_rep.setdefault(s["replica"], set()).add(
                        (s["admitted"], s["shed"]))
                for rep, states in by_rep.items():
                    assert len(states) == 1, (pname, rep, states)
            iso = doc["isolation"]
            assert iso["idle_p99_ms"] > 0 and iso["contended_p99_ms"] > 0
            print("ARTIFACT-OK", flush=True)
    """, np_=4, timeout=240)
    assert "ARTIFACT-OK" in out
