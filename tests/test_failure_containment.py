"""Gang-wide failure containment (PR 4): deadline-bounded data plane,
coordinated abort, heartbeats, timed waits, and the fault-injection
harness.

The gang tests spawn RAW worker processes (no hvtrun) so each worker's
exit code is observable independently: survivors of an injected failure
must catch ``HorovodInternalError`` within the containment bound and
exit 0, while the injected rank dies by SIGKILL. Every subprocess wait
carries a hard timeout — a containment regression fails the test
instead of stalling CI.
"""

import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build", "libhvt_core.so")

pytestmark = pytest.mark.skipif(
    not os.path.exists(LIB),
    reason="C++ engine not built (make -C horovod_tpu/csrc)")

_PORT = [26000 + (os.getpid() * 389) % 9000]


def _next_port():
    while True:
        _PORT[0] += 1
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", _PORT[0]))
                return _PORT[0]
            except OSError:
                continue


_PRELUDE = """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvt
    from horovod_tpu.engine import native
    hvt.init()
    r, n = hvt.rank(), hvt.size()
"""


def spawn_gang(body, np=2, extra_env=None, tmp_path="/tmp"):
    """Start np raw worker processes running ``body`` (after the hvt
    prelude); returns the list of Popen objects plus the per-rank log
    paths."""
    port = _next_port()
    script = textwrap.dedent(_PRELUDE.format(repo=REPO)) + \
        textwrap.dedent(body)
    path = os.path.join(str(tmp_path),
                        f"hvt_fc_{os.getpid()}_{port}.py")
    with open(path, "w") as f:
        f.write(script)
    procs, logs = [], []
    for rank in range(np):
        env = dict(os.environ)
        env.update({
            "HVT_MASTER_ADDR": "127.0.0.1",
            "HVT_MASTER_PORT": str(port),
            "HVT_PROCESS_ID": str(rank),
            "HVT_NUM_PROCESSES": str(np),
            "HVT_SHM_ALLREDUCE": "0",  # the TCP plane is under test
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",
        })
        env.update(extra_env or {})
        log = open(os.path.join(str(tmp_path),
                                f"hvt_fc_{port}_r{rank}.log"), "w+")
        procs.append(subprocess.Popen(
            [sys.executable, path], env=env, cwd=REPO, stdout=log,
            stderr=subprocess.STDOUT))
        logs.append(log)
    return procs, logs


def finish_gang(procs, logs, timeout):
    """Hard-timeout join: SIGKILL stragglers (a containment regression
    must fail, never stall CI). Returns (exit codes, per-rank output)."""
    deadline = time.time() + timeout
    codes = []
    for p in procs:
        left = max(0.1, deadline - time.time())
        try:
            codes.append(p.wait(timeout=left))
        except subprocess.TimeoutExpired:
            p.kill()
            codes.append(p.wait(timeout=30))
    outs = []
    for log in logs:
        log.flush()
        log.seek(0)
        outs.append(log.read())
        log.close()
    return codes, outs


# ------------------------------------------------------------- chaos gang

def test_chaos_kill_mid_allreduce(tmp_path):
    """The acceptance gang: HVT_FAULT_INJECT SIGKILLs rank 2 after 5
    data ops on a 4-proc gang. Every survivor must raise
    HorovodInternalError within 2x HVT_OP_TIMEOUT_MS, see the sticky
    broken state + ABORT flight-recorder event + aborts counter, fail
    fast on the next submit, and exit cleanly (no hang, no uncaught C++
    exception)."""
    op_timeout_ms = 5000
    body = """
    x = np.arange(4096, dtype=np.float32) + r
    t0 = time.monotonic()
    try:
        for i in range(30):
            hvt.allreduce(x, op=hvt.Sum, name=f"chaos.{i}")
        print("NO-ERROR", flush=True)
    except hvt.HorovodInternalError:
        dt = time.monotonic() - t0
        broken, info = native.engine_broken()
        assert broken, "broken flag not sticky"
        st = native.engine_stats()
        assert sum(st["aborts"].values()) == 1, st["aborts"]
        kinds = {e["kind_name"] for e in native.drain_events(8192)}
        assert "ABORT" in kinds, kinds
        t1 = time.monotonic()
        try:
            hvt.allreduce(x, op=hvt.Sum, name="post")
            print("POST-NO-ERROR", flush=True)
        except hvt.HorovodInternalError:
            pass
        fast = time.monotonic() - t1
        assert fast < 1.0, f"fail-fast took {fast:.2f}s"
        print(f"CAUGHT {dt:.3f} {info}", flush=True)
    hvt.shutdown()
    print("EXITED", flush=True)
    """
    procs, logs = spawn_gang(
        body, np=4, tmp_path=tmp_path,
        extra_env={"HVT_FAULT_INJECT": "kill:rank=2:after_ops=5",
                   "HVT_OP_TIMEOUT_MS": str(op_timeout_ms)})
    codes, outs = finish_gang(procs, logs,
                              timeout=4 * op_timeout_ms / 1000 + 60)
    assert codes[2] == -signal.SIGKILL, (codes, outs[2])
    for rank in (0, 1, 3):
        assert codes[rank] == 0, \
            f"survivor {rank} rc={codes[rank]}\n{outs[rank]}"
        assert "CAUGHT" in outs[rank], f"rank {rank}:\n{outs[rank]}"
        assert "EXITED" in outs[rank], f"rank {rank}:\n{outs[rank]}"
        assert "POST-NO-ERROR" not in outs[rank]
        caught = [ln for ln in outs[rank].splitlines()
                  if ln.startswith("CAUGHT")][0]
        elapsed = float(caught.split()[1])
        assert elapsed < 2 * op_timeout_ms / 1000, \
            f"rank {rank} took {elapsed:.1f}s (> 2x op timeout)"


def test_chaos_disabled_is_identical(tmp_path):
    """The same worker body with fault injection DISABLED must complete
    every op with bit-exact results — containment machinery off the
    failure path costs nothing and changes nothing."""
    body = """
    x = np.arange(4096, dtype=np.float32) + r
    exp = sum(np.arange(4096, dtype=np.float32) + i for i in range(n))
    for i in range(30):
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"chaos.{i}"))
        np.testing.assert_array_equal(res, exp)
    broken, _ = native.engine_broken()
    assert not broken
    st = native.engine_stats()
    assert sum(st["aborts"].values()) == 0, st["aborts"]
    hvt.shutdown()
    print("CLEAN", flush=True)
    """
    procs, logs = spawn_gang(body, np=4, tmp_path=tmp_path)
    codes, outs = finish_gang(procs, logs, timeout=120)
    for rank in range(4):
        assert codes[rank] == 0, f"rank {rank}\n{outs[rank]}"
        assert "CLEAN" in outs[rank]


def test_heartbeat_detects_silent_peer(tmp_path):
    """With NO collective outstanding, a silently dead peer (SIGSTOP —
    sockets stay open, no FIN) must trip the idle heartbeat on the
    survivors within ~2x HVT_HEARTBEAT_MS, and the next submit must
    raise HorovodInternalError instead of hanging."""
    hb_ms = 2000
    body = """
    x = np.ones(16, np.float32)
    hvt.allreduce(x, op=hvt.Sum, name="warm")
    if r == 2:
        import signal as _sig
        os.kill(os.getpid(), _sig.SIGSTOP)  # silent death
        time.sleep(120)
        os._exit(7)
    t0 = time.monotonic()
    while time.monotonic() - t0 < {bound}:
        broken, info = native.engine_broken()
        if broken:
            break
        time.sleep(0.1)
    assert broken, "heartbeat did not trip"
    assert "heartbeat" in info or "peer" in info, info
    print(f"DETECTED {{time.monotonic() - t0:.3f}}", flush=True)
    try:
        hvt.allreduce(x, op=hvt.Sum, name="post")
        raise SystemExit("post-abort submit did not raise")
    except hvt.HorovodInternalError:
        pass
    hvt.shutdown()
    print("EXITED", flush=True)
    """.format(bound=4 * hb_ms / 1000)
    procs, logs = spawn_gang(
        body, np=3, tmp_path=tmp_path,
        extra_env={"HVT_HEARTBEAT_MS": str(hb_ms)})
    try:
        codes = []
        for rank, p in enumerate(procs):
            if rank == 2:
                codes.append(None)
                continue
            try:
                codes.append(p.wait(timeout=5 * hb_ms / 1000 + 60))
            except subprocess.TimeoutExpired:
                p.kill()
                codes.append(p.wait(timeout=30))
    finally:
        procs[2].kill()  # SIGKILL works on a stopped process
        procs[2].wait(timeout=30)
    outs = []
    for log in logs:
        log.flush()
        log.seek(0)
        outs.append(log.read())
        log.close()
    for rank in (0, 1):
        assert codes[rank] == 0, f"rank {rank}\n{outs[rank]}"
        assert "DETECTED" in outs[rank], f"rank {rank}\n{outs[rank]}"
        det = [ln for ln in outs[rank].splitlines()
               if ln.startswith("DETECTED")][0]
        assert float(det.split()[1]) < 2 * hb_ms / 1000 + 1.0, det


def test_wait_timeout_raises_then_completes(tmp_path):
    """Handle.wait(timeout=...) (previously ignored) must raise
    HorovodTimeoutError while the peer is absent, leave the handle
    waitable, and deliver the result once the peer arrives."""
    go = os.path.join(str(tmp_path), "tw_go")
    body = """
    from horovod_tpu.engine import api as eapi
    x = np.ones(8, np.float32)
    if r == 0:
        h = eapi.allreduce(x, op=hvt.Sum, name="lone")
        t0 = time.monotonic()
        try:
            h.wait(timeout=0.4)
            raise SystemExit("timed wait did not raise")
        except hvt.HorovodTimeoutError:
            dt = time.monotonic() - t0
            assert 0.3 < dt < 5.0, dt
        assert isinstance(hvt.HorovodTimeoutError(), TimeoutError)
        open({go!r}, "w").close()
        res = np.asarray(h.wait(timeout=30))
        assert res[0] == 2.0, res[0]
        print("TIMED-OK", flush=True)
    else:
        while not os.path.exists({go!r}):
            time.sleep(0.05)
        res = np.asarray(eapi.allreduce(x, op=hvt.Sum,
                                        name="lone").wait(timeout=30))
        assert res[0] == 2.0
        print("PEER-OK", flush=True)
    hvt.shutdown()
    """.format(go=go)
    procs, logs = spawn_gang(body, np=2, tmp_path=tmp_path)
    codes, outs = finish_gang(procs, logs, timeout=90)
    assert codes == [0, 0], outs
    assert "TIMED-OK" in outs[0]
    assert "PEER-OK" in outs[1]


def test_connect_timeout_is_bounded(tmp_path):
    """A worker dialing a rank 0 that never comes up must fail init
    within the HVT_CONNECT_TIMEOUT budget (backoff + jitter, not the
    old fixed 60 s spin)."""
    port = _next_port()
    script = textwrap.dedent(f"""
        import sys, time
        sys.path.insert(0, {REPO!r})
        from horovod_tpu.engine import native
        from horovod_tpu.common.exceptions import HorovodInternalError
        t0 = time.monotonic()
        try:
            native.init_engine(rank=1, size=2,
                               master_addr="127.0.0.1",
                               master_port={port})
            raise SystemExit("init unexpectedly succeeded")
        except HorovodInternalError:
            print(f"INIT-FAILED {{time.monotonic() - t0:.2f}}",
                  flush=True)
    """)
    path = os.path.join(str(tmp_path), "connect_timeout.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"HVT_CONNECT_TIMEOUT": "2", "JAX_PLATFORMS": "cpu"})
    proc = subprocess.run([sys.executable, path], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    elapsed = float(proc.stdout.split()[-1])
    assert elapsed < 15, f"connect gave up only after {elapsed:.1f}s"


# --------------------------------------------------------- http retries

def _serve_after(port, delay_sec, payload=b'{"ok": 1}'):
    """Start an HTTP server on ``port`` after ``delay_sec`` — the
    'rendezvous still binding' scenario."""
    import http.server

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def do_PUT(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    box = {}

    def run():
        time.sleep(delay_sec)
        srv = http.server.HTTPServer(("127.0.0.1", port), H)
        box["srv"] = srv
        srv.serve_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return box


def test_http_client_retries_connection_refused():
    from horovod_tpu.runner import http_client

    port = _next_port()
    # min total backoff across 4 retries is 0.05+0.1+0.2+0.4 = 0.75 s
    box = _serve_after(port, 0.5)
    try:
        t0 = time.monotonic()
        obj = http_client.get_json(f"127.0.0.1:{port}", "/anything",
                                   timeout=2)
        assert obj == {"ok": 1}
        assert time.monotonic() - t0 < 10
        assert http_client.put_json(f"127.0.0.1:{port}", "/kv/x/y",
                                    {"a": 1}, timeout=2) == 200
    finally:
        srv = box.get("srv")
        if srv is not None:
            srv.shutdown()


def test_http_client_no_retry_fails_fast():
    from horovod_tpu.runner import http_client

    port = _next_port()  # nothing listens here
    t0 = time.monotonic()
    with pytest.raises(OSError):
        http_client.get_json(f"127.0.0.1:{port}", "/x", timeout=1,
                             retries=0)
    assert time.monotonic() - t0 < 2.0


def test_http_client_404_is_not_retried():
    import http.server

    hits = []

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            hits.append(1)
            self.send_response(404)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    port = _next_port()
    srv = http.server.HTTPServer(("127.0.0.1", port), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        from horovod_tpu.runner import http_client
        import urllib.error

        with pytest.raises(urllib.error.HTTPError):
            http_client.get_json(f"127.0.0.1:{port}", "/missing",
                                 timeout=2)
        assert len(hits) == 1, "4xx must not be retried"
    finally:
        srv.shutdown()


# ---------------------------------------------------- elastic attribution

def test_driver_blacklists_reported_failure_and_rerendezvous():
    """A survivor's /kv/failure report naming a dead rank blacklists
    that rank's host immediately, and the registry barrier then drives
    a new rendezvous round that excludes it (blacklist +
    re-rendezvous)."""
    import json

    from horovod_tpu.runner.elastic.discovery import HostDiscovery
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.settings import ElasticSettings
    from horovod_tpu.runner.http_server import RendezvousServer

    class TwoHosts(HostDiscovery):
        def find_available_hosts_and_slots(self):
            return {"hostA": 1, "hostB": 1}

    settings = ElasticSettings(min_np=1, max_np=None,
                               elastic_timeout=5.0, reset_limit=None,
                               discovery_interval=0.01)
    rendezvous = RendezvousServer()
    driver = ElasticDriver(rendezvous, TwoHosts(), settings,
                           create_worker_fn=None)
    try:
        driver.start(np=2)
        assert driver.world_size() == 2
        # a report naming a rank on the REPORTER's own host must not
        # blacklist it (process crash != lost host; keeps single-host
        # jobs recoverable)
        self_report = {"round": 1, "error": "x", "failed_ranks": [0]}
        driver._on_kv_put("failure", "hostA/0",
                          json.dumps(self_report).encode())
        assert driver.host_manager.blacklisted_count() == 0
        # hostB's worker (rank 1) dies; hostA's survivor reports it
        report = {"round": 1, "error": "hvt engine aborted (peer_lost)",
                  "failed_ranks": [1]}
        driver._on_kv_put("failure", "hostA/0",
                          json.dumps(report).encode())
        assert driver.host_manager.blacklisted_count() == 1
        # barrier: survivor READY + dead worker's exit → new round
        driver.record_ready("hostA", 0)
        driver._handle_worker_exit("hostB", 0, exit_code=137)
        deadline = time.time() + 5
        while time.time() < deadline and driver.world_size() != 1:
            time.sleep(0.02)
        assert driver.world_size() == 1
        slot = driver.get_slot_info("hostA", 0)
        assert slot is not None and slot.rank == 0
        assert driver.get_slot_info("hostB", 0) is None
    finally:
        driver.stop()


def test_failed_ranks_parsed_from_broken_reason(monkeypatch):
    import importlib

    # the elastic package re-exports the run() decorator under the
    # module's name, so attribute access yields the function — import
    # the module itself
    elastic_run = importlib.import_module("horovod_tpu.elastic.run")
    from horovod_tpu.engine import native

    monkeypatch.setattr(
        native, "engine_broken",
        lambda: (True, "peer_lost: control connection to rank 3 lost"))
    assert elastic_run._failed_ranks_from_engine() == [3]
    # remote_abort reasons name the (surviving) ORIGINATOR of the abort
    # frame, not the dead peer — they must never be reported as failed
    monkeypatch.setattr(
        native, "engine_broken",
        lambda: (True,
                 "remote_abort: abort from rank 2: hvt: recv failed "
                 "(peer lost)"))
    assert elastic_run._failed_ranks_from_engine() == []
    monkeypatch.setattr(native, "engine_broken", lambda: (False, ""))
    assert elastic_run._failed_ranks_from_engine() == []


def test_task_runner_fault_timer_arming():
    from horovod_tpu.runner.task_runner import maybe_arm_fault_timer

    # wrong rank / no after_sec / engine-owned specs never arm
    assert maybe_arm_fault_timer(0, "kill:rank=1:after_sec=5") is None
    assert maybe_arm_fault_timer(2, "kill:rank=2:after_ops=5") is None
    assert maybe_arm_fault_timer(2, "drop_conn:rank=2") is None
    assert maybe_arm_fault_timer(0, None) is None
    t = maybe_arm_fault_timer(1, "kill:rank=1:after_sec=600")
    assert t is not None
    t.cancel()
