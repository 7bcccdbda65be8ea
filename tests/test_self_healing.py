"""Self-healing links (transport.h): transparent reconnect with
sequence-numbered replay, the abort/recovery boundary, and the
transient-fault chaos specs.

Gang tests reuse the raw-Popen harness of test_failure_containment
(independent exit codes, hard timeouts). The invariant under every
TRANSIENT fault: the run completes **bit-identically** to an
injection-off run with ≥1 recorded reconnect and ZERO aborts; the
invariant at the boundary: exhausted budgets escalate into the PR 4
coordinated abort with a reason naming the peer and the budget.
"""

import os
import signal

import pytest

from test_failure_containment import LIB, finish_gang, spawn_gang

pytestmark = pytest.mark.skipif(
    not os.path.exists(LIB),
    reason="C++ engine not built (make -C horovod_tpu/csrc)")


def _uring_ok():
    try:
        from horovod_tpu.engine import native
        return native.uring_supported()
    except Exception:
        return False


# The session-layer contracts (replay after drop, epoch handshake,
# abort/recovery boundary, shutdown-during-reconnect) must hold
# verbatim under every link backend — IoUringLink swaps only the byte
# movement under PumpDuplex, so the specs below run per backend, with
# io_uring skipped cleanly where the kernel probe fails.
BACKENDS = ["tcp", pytest.param("io_uring", marks=pytest.mark.skipif(
    not _uring_ok(), reason="io_uring kernel probe failed"))]


def _all_exited_0_and_said(word, codes, outs):
    """A failure shows every rank's output: the rank that aborted first,
    and says why, is rarely the one whose exit code is read first."""
    for rank, code in enumerate(codes):
        assert code == 0 and word in outs[rank], (
            f"rank {rank} exited {code}\n" + "\n".join(
                f"---- rank {r} ----\n{out}" for r, out in enumerate(outs)))


# ------------------------------------------------------- transient heals

@pytest.mark.parametrize("backend", BACKENDS)
def test_flaky_conn_heals_bit_identical(tmp_path, backend):
    """The acceptance gang: flaky_conn cuts rank 1's links mid-allreduce
    (tx- and rx-side, twice). Every rank must finish all ops with
    bit-exact results, ≥1 RECONNECT event recorded on the cut ranks,
    and zero ABORT events / abort counters anywhere."""
    body = """
    x = np.arange(262144, dtype=np.float32) + r
    exp = sum(np.arange(262144, dtype=np.float32) + i for i in range(n))
    for i in range(10):
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"fl.{i}"))
        np.testing.assert_array_equal(res, exp)
    st = native.engine_stats()
    broken, info = native.engine_broken()
    assert not broken, info
    assert sum(st["aborts"].values()) == 0, st["aborts"]
    kinds = [e["kind_name"] for e in native.drain_events(8192)]
    assert "ABORT" not in kinds
    rec = sum(st["link_reconnects"].values())
    print(f"RECONNECTS {rec} REPLAY {st['replay_bytes']}", flush=True)
    if r == 1:
        assert rec >= 1, st["link_reconnects"]
        assert "RECONNECT" in kinds, sorted(set(kinds))
    hvt.shutdown()
    print("CLEAN", flush=True)
    """
    procs, logs = spawn_gang(
        body, np=4, tmp_path=tmp_path,
        extra_env={"HVT_FAULT_INJECT": "flaky_conn:rank=1:count=2:after_ops=3",
                   "HVT_LINK_BACKEND": backend,
                   "HVT_OP_TIMEOUT_MS": "30000"})
    codes, outs = finish_gang(procs, logs, timeout=150)
    _all_exited_0_and_said("CLEAN", codes, outs)


def test_reset_storm_survives(tmp_path):
    """reset_storm resets one data link every 3 data ops on every rank —
    sustained connection churn must still produce bit-exact results
    with zero aborts."""
    body = """
    x = np.arange(16384, dtype=np.float32) * (r + 1)
    exp = sum(np.arange(16384, dtype=np.float32) * (i + 1)
              for i in range(n))
    for i in range(12):
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"rs.{i}"))
        np.testing.assert_array_equal(res, exp)
    st = native.engine_stats()
    assert sum(st["aborts"].values()) == 0, st["aborts"]
    print(f"RECONNECTS {sum(st['link_reconnects'].values())}", flush=True)
    hvt.shutdown()
    print("CLEAN", flush=True)
    """
    procs, logs = spawn_gang(
        body, np=4, tmp_path=tmp_path,
        extra_env={"HVT_FAULT_INJECT": "reset_storm:every_ops=3",
                   "HVT_OP_TIMEOUT_MS": "30000"})
    codes, outs = finish_gang(procs, logs, timeout=150)
    _all_exited_0_and_said("CLEAN", codes, outs)
    recon = sum(int(ln.split()[1]) for out in outs for ln in out.splitlines()
                if ln.startswith("RECONNECTS"))
    assert recon >= 1, f"storm never cut a link\n{outs}"


def test_partition_heals_after_hold(tmp_path):
    """partition:hosts=A|B:ms=300 cuts the cross-'host' links (faked
    topology on loopback) and holds reconnects 300 ms; the gang must
    heal by itself — zero aborts, results exact, and the RECONNECT
    event's duration reflects the hold."""
    body = """
    x = np.arange(32768, dtype=np.float32) + 3 * r
    exp = sum(np.arange(32768, dtype=np.float32) + 3 * i
              for i in range(n))
    for i in range(8):
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"pt.{i}"))
        np.testing.assert_array_equal(res, exp)
    st = native.engine_stats()
    assert sum(st["aborts"].values()) == 0, st["aborts"]
    evs = [e for e in native.drain_events(8192)
           if e["kind_name"] == "RECONNECT"]
    print(f"RECONNECTS {sum(st['link_reconnects'].values())} "
          f"DUR {max([e['arg2'] for e in evs], default=0)}", flush=True)
    hvt.shutdown()
    print("CLEAN", flush=True)
    """
    extra = {"HVT_FAULT_INJECT": "partition:hosts=hA|hB:ms=300",
             "HVT_OP_TIMEOUT_MS": "30000"}
    procs, logs = [], []
    # per-rank env: fake ranks 0-1 onto host hA, ranks 2-3 onto hB
    import test_failure_containment as fc
    port = fc._next_port()
    import sys
    import textwrap
    script = textwrap.dedent(fc._PRELUDE.format(repo=fc.REPO)) + \
        textwrap.dedent(body)
    path = os.path.join(str(tmp_path), f"hvt_part_{port}.py")
    with open(path, "w") as f:
        f.write(script)
    import subprocess
    for rank in range(4):
        env = dict(os.environ)
        env.update({
            "HVT_MASTER_ADDR": "127.0.0.1",
            "HVT_MASTER_PORT": str(port),
            "HVT_PROCESS_ID": str(rank),
            "HVT_NUM_PROCESSES": "4",
            "HVT_SHM_ALLREDUCE": "0",
            "HVT_HIERARCHICAL_ALLREDUCE": "0",  # flat ring across "hosts"
            "HVT_TOPO_HOST": "hA" if rank < 2 else "hB",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",
        })
        env.update(extra)
        log = open(os.path.join(str(tmp_path),
                                f"hvt_part_{port}_r{rank}.log"), "w+")
        procs.append(subprocess.Popen(
            [sys.executable, path], env=env, cwd=fc.REPO, stdout=log,
            stderr=subprocess.STDOUT))
        logs.append(log)
    codes, outs = finish_gang(procs, logs, timeout=150)
    _all_exited_0_and_said("CLEAN", codes, outs)
    durs = [int(ln.split()[3]) for out in outs for ln in out.splitlines()
            if ln.startswith("RECONNECTS")]
    # at least one rank's heal waited out the (ranks-local) 300 ms hold
    assert max(durs) >= 200_000, durs


def test_tree_mode_member_link_heals_via_leader_reaccept(tmp_path):
    """HVT_CTRL_TOPOLOGY=tree: flaky_conn on a MEMBER cuts its link to
    the host leader; the leader must RE-ACCEPT on its (kept-open) tree
    listener and the negotiation stream must resume — zero aborts,
    exact results, ≥1 ctrl-plane reconnect on the member."""
    body = """
    x = np.arange(65536, dtype=np.float32) + r
    exp = sum(np.arange(65536, dtype=np.float32) + i for i in range(n))
    for i in range(10):
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"tr.{i}"))
        np.testing.assert_array_equal(res, exp)
    st = native.engine_stats()
    assert sum(st["aborts"].values()) == 0, st["aborts"]
    if r == 3:  # the cut member: its tree-parent link must have healed
        assert st["link_reconnects"]["ctrl"] >= 1, st["link_reconnects"]
    hvt.shutdown()
    print("CLEAN", flush=True)
    """
    import subprocess
    import sys
    import textwrap
    import test_failure_containment as fc
    port = fc._next_port()
    script = textwrap.dedent(fc._PRELUDE.format(repo=fc.REPO)) + \
        textwrap.dedent(body)
    path = os.path.join(str(tmp_path), f"hvt_tree_{port}.py")
    with open(path, "w") as f:
        f.write(script)
    procs, logs = [], []
    for rank in range(4):
        env = dict(os.environ)
        env.update({
            "HVT_MASTER_ADDR": "127.0.0.1",
            "HVT_MASTER_PORT": str(port),
            "HVT_PROCESS_ID": str(rank),
            "HVT_NUM_PROCESSES": "4",
            "HVT_SHM_ALLREDUCE": "0",
            "HVT_HIERARCHICAL_ALLREDUCE": "0",
            "HVT_CTRL_TOPOLOGY": "tree",
            # hosts hA={0,1}, hB={2,3}: rank 2 leads hB, rank 3 is its
            # member — the rank the fault cuts
            "HVT_TOPO_HOST": "hA" if rank < 2 else "hB",
            "HVT_FAULT_INJECT": "flaky_conn:rank=3:count=2:after_ops=3",
            "HVT_OP_TIMEOUT_MS": "30000",
            # the heal is what is tested, not its speed: in a whole run six
            # workers hold the cores and the leader's re-accept has outlasted
            # the default 10 dials in 10 s (two whole runs of three, PR 61);
            # still inside the op deadline
            "HVT_LINK_RETRIES": "40",
            "HVT_LINK_RETRY_WINDOW_MS": "25000",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",
        })
        log = open(os.path.join(str(tmp_path),
                                f"hvt_tree_{port}_r{rank}.log"), "w+")
        procs.append(subprocess.Popen(
            [sys.executable, path], env=env, cwd=fc.REPO, stdout=log,
            stderr=subprocess.STDOUT))
        logs.append(log)
    codes, outs = finish_gang(procs, logs, timeout=150)
    _all_exited_0_and_said("CLEAN", codes, outs)


# ------------------------------------------------- abort/recovery boundary

@pytest.mark.parametrize("backend", BACKENDS)
def test_replay_budget_exhaustion_escalates(tmp_path, backend):
    """An rx-side cut mid-4MB-transfer loses far more than a 256-byte
    replay ring can cover: the link must ESCALATE into the coordinated
    abort with a reason naming the peer and HVT_REPLAY_BUDGET_BYTES —
    never hang, never deliver wrong data."""
    body = """
    x = np.arange(1 << 20, dtype=np.float32) + r
    try:
        for i in range(10):
            hvt.allreduce(x, op=hvt.Sum, name=f"bx.{i}")
        print("NO-ERROR", flush=True)
    except hvt.HorovodInternalError:
        broken, info = native.engine_broken()
        assert broken
        print(f"CAUGHT {info}", flush=True)
    hvt.shutdown()
    print("EXITED", flush=True)
    """
    procs, logs = spawn_gang(
        body, np=4, tmp_path=tmp_path,
        extra_env={"HVT_FAULT_INJECT": "flaky_conn:rank=1:count=2:after_ops=2",
                   "HVT_LINK_BACKEND": backend,
                   "HVT_REPLAY_BUDGET_BYTES": "256",
                   "HVT_SOCK_BUF": "262144",
                   "HVT_OP_TIMEOUT_MS": "15000",
                   "HVT_LINK_RETRY_WINDOW_MS": "4000"})
    codes, outs = finish_gang(procs, logs, timeout=150)
    blob = "\n".join(outs)
    _all_exited_0_and_said("EXITED", codes, outs)
    # the cut rank (or its peer) must have named the budget in the abort
    assert "replay budget exhausted" in blob, blob
    assert "HVT_REPLAY_BUDGET_BYTES=256" in blob, blob


def test_reconnect_disabled_restores_pr4_abort(tmp_path):
    """HVT_LINK_RECONNECT=0: the same transient cut becomes a
    coordinated peer_lost abort on the PR 4 path — the parity
    baseline."""
    body = """
    x = np.arange(65536, dtype=np.float32) + r
    try:
        for i in range(10):
            hvt.allreduce(x, op=hvt.Sum, name=f"nr.{i}")
        print("NO-ERROR", flush=True)
    except hvt.HorovodInternalError:
        st = native.engine_stats()
        assert st["aborts"]["peer_lost"] + st["aborts"]["remote_abort"] \
            >= 1, st["aborts"]
        print("CAUGHT", flush=True)
    hvt.shutdown()
    print("EXITED", flush=True)
    """
    procs, logs = spawn_gang(
        body, np=4, tmp_path=tmp_path,
        extra_env={"HVT_FAULT_INJECT": "flaky_conn:rank=1:count=1:after_ops=2",
                   "HVT_LINK_RECONNECT": "0",
                   "HVT_OP_TIMEOUT_MS": "10000"})
    codes, outs = finish_gang(procs, logs, timeout=120)
    _all_exited_0_and_said("EXITED", codes, outs)
    assert sum(out.count("CAUGHT") for out in outs) >= 1, outs


@pytest.mark.parametrize("backend", BACKENDS)
def test_shutdown_during_inflight_reconnect_exits_cleanly(tmp_path, backend):
    """A partition with a long hold parks the engine thread inside a
    reconnect episode; hvt.shutdown() must cut it short (the hub stop
    gate) and the process must exit 0 promptly — no join hang, no
    crash."""
    body = """
    import threading
    x = np.arange(32768, dtype=np.float32) + r
    h = hvt.allreduce_async(x, op=hvt.Sum, name="sd.0")
    time.sleep(1.5)  # the partition fires on op 1 and holds 60 s
    t0 = time.monotonic()
    hvt.shutdown()
    dt = time.monotonic() - t0
    assert dt < 20, f"shutdown took {dt:.1f}s"
    print(f"SHUTDOWN {dt:.2f}", flush=True)
    """
    import subprocess
    import sys
    import textwrap
    import test_failure_containment as fc
    port = fc._next_port()
    script = textwrap.dedent(fc._PRELUDE.format(repo=fc.REPO)) + \
        textwrap.dedent(body)
    path = os.path.join(str(tmp_path), f"hvt_sd_{port}.py")
    with open(path, "w") as f:
        f.write(script)
    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "HVT_MASTER_ADDR": "127.0.0.1",
            "HVT_MASTER_PORT": str(port),
            "HVT_PROCESS_ID": str(rank),
            "HVT_NUM_PROCESSES": "2",
            "HVT_SHM_ALLREDUCE": "0",
            "HVT_HIERARCHICAL_ALLREDUCE": "0",
            "HVT_TOPO_HOST": "hA" if rank == 0 else "hB",
            "HVT_FAULT_INJECT": "partition:hosts=hA|hB:ms=60000",
            "HVT_LINK_BACKEND": backend,
            "HVT_OP_TIMEOUT_MS": "30000",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",
        })
        log = open(os.path.join(str(tmp_path),
                                f"hvt_sd_{port}_r{rank}.log"), "w+")
        procs.append(subprocess.Popen(
            [sys.executable, path], env=env, cwd=fc.REPO, stdout=log,
            stderr=subprocess.STDOUT))
        logs.append(log)
    codes, outs = finish_gang(procs, logs, timeout=90)
    _all_exited_0_and_said("SHUTDOWN", codes, outs)


def test_sigkill_still_converges_one_deadline(tmp_path):
    """The PR 4 acceptance boundary with self-healing ON: a SIGKILLed
    rank must still turn into HorovodInternalError on every survivor
    within ~2x the op deadline (dead-peer dials are refused fast; the
    aborting ranks close their listeners so the cascade stays on the
    PR 4 clock)."""
    op_timeout_ms = 6000
    body = """
    x = np.arange(4096, dtype=np.float32) + r
    t0 = time.monotonic()
    try:
        for i in range(30):
            hvt.allreduce(x, op=hvt.Sum, name=f"sk.{i}")
        print("NO-ERROR", flush=True)
    except hvt.HorovodInternalError:
        dt = time.monotonic() - t0
        print(f"CAUGHT {dt:.3f}", flush=True)
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
        assert codes[rank] == 0, f"rank {rank}\n{outs[rank]}"
        assert "CAUGHT" in outs[rank], f"rank {rank}\n{outs[rank]}"
        caught = [ln for ln in outs[rank].splitlines()
                  if ln.startswith("CAUGHT")][0]
        elapsed = float(caught.split()[1])
        assert elapsed < 2 * op_timeout_ms / 1000, \
            f"rank {rank} took {elapsed:.1f}s (> 2x op timeout)"


# --------------------------------------------------------- observability

@pytest.mark.parametrize("backend", BACKENDS)
def test_diagnostics_reports_link_state(tmp_path, backend):
    """hvt.diagnostics()['links'] / debugz: every link carries
    peer/plane/state/retries/epoch/in_state_sec, and a healed link
    shows a bumped session epoch."""
    body = """
    x = np.arange(65536, dtype=np.float32) + r
    for i in range(8):
        hvt.allreduce(x, op=hvt.Sum, name=f"dg.{i}")
    hvt.allreduce(x, op=hvt.Sum, name="dg.9")
    n_ctrl = (n - 1) if r == 0 else 1
    n_data = n - 1
    # UpdateDiag refreshes the snapshot at 10 Hz when the engine thread
    # gets to run: poll for the healed link, do not sleep and hope
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        d = native.diagnostics()
        links = d.get("links") or []
        if len(links) == n_ctrl + n_data and (
                r != 1 or any(l["epoch"] >= 1 for l in links)):
            break
        time.sleep(0.05)
    assert len(links) == n_ctrl + n_data, (r, d)
    for l in links:
        assert l["plane"] in ("ctrl", "data"), l
        assert l["state"] in ("healthy", "reconnecting", "dead"), l
        assert l["in_state_sec"] >= 0, l
        assert "retries" in l and "epoch" in l, l
    if r == 1:
        assert any(l["epoch"] >= 1 for l in links), links
    hvt.shutdown()
    print("CLEAN", flush=True)
    """
    procs, logs = spawn_gang(
        body, np=3, tmp_path=tmp_path,
        extra_env={"HVT_FAULT_INJECT": "flaky_conn:rank=1:count=1:after_ops=3",
                   "HVT_LINK_BACKEND": backend,
                   "HVT_OP_TIMEOUT_MS": "30000"})
    codes, outs = finish_gang(procs, logs, timeout=120)
    _all_exited_0_and_said("CLEAN", codes, outs)
