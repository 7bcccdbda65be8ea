"""Multi-process engine integration tests — the analog of the reference's
``test/parallel`` suite run under ``horovodrun -np 2`` on loopback
(``test/integration/test_static_run.py:182``). Each test launches real
processes through the hvtrun launcher and asserts on their exits/output."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build", "libhvt_core.so")

pytestmark = pytest.mark.skipif(
    not os.path.exists(LIB),
    reason="C++ engine not built (make -C horovod_tpu/csrc)")

# Per-pytest-process port base: two concurrent pytest invocations (e.g. a
# stress loop alongside a normal run) must not race for the same master
# port — rank 0's control/coordinator listener binds it exclusively. The
# pid spreads bases apart; _next_port() additionally probe-binds so a
# base collision degrades to a skipped port, not a failed test.
_PORT = [20000 + (os.getpid() * 641) % 10000]


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


def run_workers(body, np=2, timeout=90, extra_env=None, expect_rc=0,
                launcher_args=()):
    """Write a worker script and launch it with hvtrun -np N."""
    _next_port()
    script = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, {REPO!r})
        import numpy as np
        import horovod_tpu as hvt
        hvt.init()
        r, n = hvt.rank(), hvt.size()
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print(f"WORKER-{{r}}-DONE", flush=True)
        hvt.shutdown()
    """)
    path = f"/tmp/hvt_itest_{os.getpid()}_{_PORT[0]}.py"
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", str(np),
         "--master-port", str(_PORT[0]), *launcher_args,
         sys.executable, path],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == expect_rc, \
        f"rc={proc.returncode}\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout + proc.stderr


def test_allreduce_average_2proc():
    out = run_workers("""
        x = np.full((5,), float(r + 1), np.float32)
        res = np.asarray(hvt.allreduce(x, name="t"))
        np.testing.assert_allclose(res, (1 + n) / 2.0)
    """)
    assert "WORKER-0-DONE" in out and "WORKER-1-DONE" in out


def test_dtypes_roundtrip_2proc():
    run_workers("""
        for dt in (np.float32, np.float64, np.int32, np.int64, np.float16):
            x = (np.arange(6) + r).astype(dt)
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"d{dt.__name__}"))
            expected = sum((np.arange(6) + i).astype(dt) for i in range(n))
            np.testing.assert_allclose(res.astype(np.float64),
                                       expected.astype(np.float64))
    """)


def test_allgather_uneven_2proc():
    run_workers("""
        rows = r + 1
        res = np.asarray(hvt.allgather(np.full((rows, 3), float(r),
                                       np.float32), name="ag"))
        assert res.shape == (3, 3), res.shape
        np.testing.assert_allclose(res[0], 0.0)
        np.testing.assert_allclose(res[1:], 1.0)
    """)


def test_alltoall_splits_2proc():
    run_workers("""
        splits = [1, 2]
        payload = np.asarray([[float(r)], [float(r) + 10], [float(r) + 10]],
                             np.float32)
        out, rsplits = hvt.alltoall(payload, splits=splits, name="a2a")
        out = np.asarray(out)
        if r == 0:
            assert list(rsplits) == [1, 1]
            np.testing.assert_allclose(out[:, 0], [0.0, 1.0])
        else:
            assert list(rsplits) == [2, 2]
            np.testing.assert_allclose(out[:, 0], [10.0, 10.0, 11.0, 11.0])
    """)


def test_consistency_error_not_hang_2proc():
    # reference behavior: cross-rank shape mismatch → per-tensor error
    # delivered to the caller, not a deadlock (controller.cc:481-706)
    run_workers("""
        try:
            hvt.allreduce(np.zeros((r + 2,), np.float32), name="bad")
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "mismatched shape" in str(e)
    """)


def test_adasum_2proc():
    run_workers("""
        if r == 0:
            x = np.asarray([1.0, 0.0], np.float32)
        else:
            x = np.asarray([0.0, 1.0], np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Adasum, name="ada"))
        np.testing.assert_allclose(res, [1.0, 1.0], rtol=1e-5)
    """)


def test_adasum_start_level_2proc():
    """HVT_ADASUM_START_LEVEL: levels below it average instead of
    adasum-combining (reference GPU composition, adasum.h:177-183) — with
    2 ranks and start level 2, the result is the plain mean."""
    run_workers("""
        x = np.asarray([4.0, 0.0], np.float32) if r == 0 else \
            np.asarray([0.0, 2.0], np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Adasum, name="asl"))
        np.testing.assert_allclose(res, [2.0, 1.0], rtol=1e-6)
    """, extra_env={"HVT_ADASUM_START_LEVEL": "2"})


def test_join_with_cached_hit_does_not_starve_2proc():
    """Liveness pin: a rank announcing a CACHED HIT while the peer joins
    must still complete. The all-ranks-hit fast path can never fire once
    a rank is joined (it will never announce), so the coordinator must
    fold outstanding hits into slow-path negotiation whose required count
    excludes joined ranks (engine.cc Coordinate else-branch). Before that
    fold existed this wedged deterministically: step 1 caches 'g', rank 1
    joins, rank 0's second submit of 'g' is a hit that waits forever for
    a peer hit that cannot come."""
    run_workers("""
        # step 1: negotiate + cache 'g' on both ranks
        res = np.asarray(hvt.allreduce(np.ones((3,), np.float32),
                                       op=hvt.Sum, name="g"))
        np.testing.assert_allclose(res, 2.0)
        if r == 0:
            # step 2: identical params → cache hit, peer joined → zeros
            res = np.asarray(hvt.allreduce(np.ones((3,), np.float32),
                                           op=hvt.Sum, name="g"))
            np.testing.assert_allclose(res, 1.0)
        last = hvt.join()
        assert last == 0, last
    """)


def test_async_submit_then_join_pairs_with_late_peer_2proc():
    """Correctness pin (round-4 review finding): an announcement from a
    since-joined rank must NOT stand in for an active rank that never
    announced. Rank 1 submits 'g' async then joins; rank 0 submits 'g'
    later. The collective must pair BOTH submissions (each rank sees the
    full sum), not fire per-rank half-results: completion requires every
    ACTIVE participant individually seen (engine.cc slow-path all_seen),
    not a raw announcement count."""
    run_workers("""
        import time
        # step 1: negotiate + cache 'g' so rank 1's re-submit is a hit
        res = np.asarray(hvt.allreduce(np.ones((4,), np.float32),
                                       op=hvt.Sum, name="g"))
        np.testing.assert_allclose(res, 2.0)
        if r == 1:
            h = hvt.allreduce_async(np.full((4,), 5.0, np.float32),
                                    op=hvt.Sum, name="g")
            last = hvt.join()
            res = np.asarray(hvt.synchronize(h))
            np.testing.assert_allclose(res, 8.0)  # 5 (self) + 3 (rank 0)
        else:
            time.sleep(0.5)  # let rank 1's announce + join land first
            res = np.asarray(hvt.allreduce(np.full((4,), 3.0, np.float32),
                                           op=hvt.Sum, name="g"))
            np.testing.assert_allclose(res, 8.0)
            last = hvt.join()
        assert last == 0, last
    """)


def test_join_uneven_steps_2proc():
    # rank 1 runs fewer steps then joins; rank 0 keeps reducing
    # (reference Join semantics, operations.cc:1164)
    run_workers("""
        steps = 4 if r == 0 else 2
        for i in range(steps):
            res = np.asarray(hvt.allreduce(np.ones((3,), np.float32),
                                           op=hvt.Sum, name=f"step{i}"))
            if i < 2:
                np.testing.assert_allclose(res, 2.0)
            else:
                np.testing.assert_allclose(res, 1.0)  # peer joined → zeros
        last = hvt.join()
        assert last == 0, last  # rank 0 ran more steps → joined last
    """)


def test_broadcast_object_and_state_sync_2proc():
    run_workers("""
        obj = hvt.broadcast_object({"epoch": 3} if r == 0 else None,
                                   root_rank=0)
        assert obj == {"epoch": 3}
        objs = hvt.allgather_object(("rank", r))
        assert objs == [("rank", 0), ("rank", 1)]
    """)


def test_stall_inspector_warns():
    # rank 1 never submits "lonely"; rank 0 should see a stall warning, then
    # both proceed after rank 1 submits late
    out = run_workers("""
        import time
        if r == 0:
            h = hvt.allreduce_async(np.ones((2,), np.float32), name="lonely")
        time.sleep(2.5)
        if r == 1:
            h = hvt.allreduce_async(np.ones((2,), np.float32), name="lonely")
        res = np.asarray(hvt.synchronize(h))
        np.testing.assert_allclose(res, 1.0)
    """, launcher_args=("--stall-warning-sec", "1"))
    assert "possible stall" in out


def test_worker_crash_fails_job():
    # a worker exiting mid-collective must fail the whole job, not hang —
    # the engine surfaces peer loss as an error (HorovodInternalError path)
    out = run_workers("""
        if r == 1:
            os._exit(17)
        try:
            hvt.allreduce(np.ones((2,), np.float32), name="x")
        except Exception as e:
            print("GOT-ERROR", type(e).__name__, flush=True)
            raise SystemExit(1)
    """, expect_rc=1, timeout=60)
    assert "GOT-ERROR" in out or "ranks failed" in out


def test_allreduce_dtype_matrix_2proc():
    """Every wire dtype allreduces correctly (the reference sweeps dtypes
    across its parallel suites, e.g. test_torch.py/test_tensorflow.py)."""
    out = run_workers("""
        import ml_dtypes
        cases = [
            ("float32", np.float32, 1e-6),
            ("float64", np.float64, 1e-12),
            ("float16", np.float16, 1e-2),
            ("bfloat16", ml_dtypes.bfloat16, 1e-1),
            ("int32", np.int32, 0),
            ("int64", np.int64, 0),
            ("uint8", np.uint8, 0),
        ]
        for dname, dt, tol in cases:
            x = (np.arange(8) % 4 + r + 1).astype(dt)
            res = np.asarray(hvt.allreduce(x, name=f"dt.{dname}",
                                           average=False))
            expect = sum((np.arange(8) % 4 + rr + 1).astype(np.float64)
                         for rr in range(n))
            np.testing.assert_allclose(
                np.asarray(res, np.float64), expect, atol=float(tol),
                err_msg=dname)
            assert res.dtype == np.dtype(dt), (dname, res.dtype)
        print(f"DTYPES-OK-{r}", flush=True)
    """)
    assert "DTYPES-OK-0" in out and "DTYPES-OK-1" in out


def _run_raw(script_body, np_=4, extra_env=None, timeout=120):
    """Launch a raw worker script (no run_workers template) — for tests
    that must set per-rank env before hvt.init()."""
    _PORT[0] += 1
    path = f"/tmp/hvt_raw_{os.getpid()}_{_PORT[0]}.py"
    with open(path, "w") as f:
        f.write(script_body)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
         str(np_), "--master-port", str(_PORT[0]), sys.executable, path],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"rc={proc.returncode}\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout + proc.stderr


_HIER_BODY = f"""
import os, sys
sys.path.insert(0, {REPO!r})
rank = int(os.environ["HVT_PROCESS_ID"])
os.environ["HVT_TOPO_HOST"] = "hostA" if rank < 2 else "hostB"
import numpy as np
import horovod_tpu as hvt
hvt.init()
r, n = hvt.rank(), hvt.size()
assert n == 4
# integer payloads are exact in fp32: hierarchical must match the flat
# ring (and the analytic expectation) bitwise
for name, count in [("a", 1), ("b", 5), ("c", 64), ("d", 1000)]:
    x = (np.arange(count) % 7 + r + 1).astype(np.float32)
    res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=name))
    expect = sum(
        (np.arange(count) % 7 + rr + 1) for rr in range(n)).astype(
        np.float32)
    np.testing.assert_array_equal(res, expect)
# fused unit (several tensors in one cycle) through the same path
hs = [hvt.allreduce_async(np.full((16,), float(r + 1 + i), np.float32),
                          op=hvt.Sum, name=f"f{{i}}") for i in range(3)]
for i, h in enumerate(hs):
    np.testing.assert_array_equal(
        np.asarray(hvt.synchronize(h)),
        np.full((16,), float(sum(rr + 1 + i for rr in range(n))),
                np.float32))
mx = np.asarray(hvt.allreduce(np.float32([r]), op=hvt.Max, name="mx"))
np.testing.assert_array_equal(mx, [3.0])
avg = np.asarray(hvt.allreduce(np.full((8,), float(r + 1), np.float32),
                               name="avg"))
np.testing.assert_allclose(avg, 2.5)
d64 = np.asarray(hvt.allreduce(np.arange(10, dtype=np.float64) + r,
                               op=hvt.Sum, name="d64"))
np.testing.assert_array_equal(d64, np.arange(10, dtype=np.float64) * 4 + 6)
print(f"HIER-OK-{{r}}", flush=True)
hvt.shutdown()
"""


def test_hierarchical_allreduce_2x2_topology():
    """Faked 2-host x 2-slot topology (HVT_TOPO_HOST): the hierarchical
    backend (local reduce-scatter -> cross allreduce -> local allgather,
    reference nccl_operations.cc:188-350) must engage and produce results
    identical to the flat ring's."""
    out = _run_raw(_HIER_BODY, extra_env={"HVT_LOG_LEVEL": "info"})
    assert "hierarchical allreduce (2x2)" in out, out
    for r in range(4):
        assert f"HIER-OK-{r}" in out


def test_hierarchical_serves_reducescatter_2x2():
    """Reducescatter lowers to allreduce at the engine, so on a faked
    2-host topology it must ride the hierarchical decomposition and slice
    the right shard."""
    extra = """
rs = np.asarray(hvt.reducescatter(
    (np.arange(8, dtype=np.float32) + r).reshape(8, 1), op=hvt.Sum,
    name="hier.rs"))
full = sum((np.arange(8, dtype=np.float32) + rr).reshape(8, 1)
           for rr in range(n))
np.testing.assert_array_equal(rs, full[r * 2:(r + 1) * 2])
print(f"HIER-RS-OK-{r}", flush=True)
"""
    body = _HIER_BODY.replace("hvt.shutdown()", extra + "hvt.shutdown()")
    out = _run_raw(body, extra_env={"HVT_LOG_LEVEL": "info"})
    assert "hierarchical allreduce (2x2)" in out, out
    for r in range(4):
        assert f"HIER-RS-OK-{r}" in out


def test_hierarchical_disabled_falls_back_to_ring():
    """HVT_HIERARCHICAL_ALLREDUCE=0 keeps the ordered backend list on the
    ring fallback; results unchanged."""
    out = _run_raw(_HIER_BODY, extra_env={
        "HVT_LOG_LEVEL": "info", "HVT_HIERARCHICAL_ALLREDUCE": "0"})
    assert "hierarchical allreduce" not in out, out
    for r in range(4):
        assert f"HIER-OK-{r}" in out


def test_grouped_allreduce_single_ring_op_2proc():
    """A 3-tensor group must fuse into ONE data-plane collective even when
    the fusion threshold is too small for threshold-based fusion
    (deterministic group fusion, reference controller.cc:199-223)."""
    out = run_workers("""
        from horovod_tpu.engine import native
        base = native.engine_data_ops()
        xs = [np.full((4,), float(r + 1 + i), np.float32) for i in range(3)]
        res = hvt.grouped_allreduce(xs, op=hvt.Sum, name="grp")
        for i, t in enumerate(res):
            expect = sum(float(rr + 1 + i) for rr in range(n))
            np.testing.assert_allclose(np.asarray(t), expect)
        ops = native.engine_data_ops() - base
        assert ops == 1, f"expected 1 fused ring op for the group, got {ops}"
        print(f"GROUP-OK-{r}", flush=True)
    """, extra_env={"HVT_FUSION_THRESHOLD": "1"})
    assert "GROUP-OK-0" in out and "GROUP-OK-1" in out


def test_grouped_allreduce_disable_group_fusion_2proc():
    """HVT_DISABLE_GROUP_FUSION keeps group members un-merged (3 ring ops)
    while negotiation stays atomic."""
    out = run_workers("""
        from horovod_tpu.engine import native
        base = native.engine_data_ops()
        xs = [np.full((4,), float(i + 1), np.float32) for i in range(3)]
        res = hvt.grouped_allreduce(xs, op=hvt.Sum, name="grp")
        for i, t in enumerate(res):
            np.testing.assert_allclose(np.asarray(t), float(i + 1) * n)
        ops = native.engine_data_ops() - base
        assert ops == 3, f"expected 3 unmerged ring ops, got {ops}"
        print(f"NOFUSE-OK-{r}", flush=True)
    """, extra_env={"HVT_FUSION_THRESHOLD": "1",
                    "HVT_DISABLE_GROUP_FUSION": "1"})
    assert "NOFUSE-OK-0" in out and "NOFUSE-OK-1" in out


def test_grouped_member_mismatch_poisons_group_2proc():
    """A cross-rank shape mismatch on ONE member must error the WHOLE
    group (all-or-nothing), not deadlock the remaining members."""
    run_workers("""
        xs = [np.zeros((2,), np.float32),
              np.zeros((r + 2,), np.float32),   # mismatched across ranks
              np.zeros((2,), np.float32)]
        try:
            hvt.grouped_allreduce(xs, op=hvt.Sum, name="badgrp")
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "mismatched shape" in str(e) or "aborted" in str(e), e
    """)


def test_process_sets_4proc():
    """Eager collectives over process subsets (later-lineage horovod
    ProcessSet semantics on the engine path): disjoint sets run
    concurrently; allgather/broadcast/alltoall/reducescatter follow the
    set's positional layout; non-members must not call."""
    out = run_workers("""
        from horovod_tpu.common.process_sets import ProcessSet
        evens = ProcessSet([0, 2])
        odds = ProcessSet([1, 3])
        mine = evens if r % 2 == 0 else odds

        # disjoint subset allreduces proceed concurrently
        x = np.full((4,), float(r + 1), np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="ps",
                                       process_set=mine))
        expect = (1 + 3) if r % 2 == 0 else (2 + 4)
        np.testing.assert_allclose(res, float(expect))

        # average divides by the SET size, not the world size
        avg = np.asarray(hvt.allreduce(x, name="psavg", process_set=mine))
        np.testing.assert_allclose(avg, expect / 2.0)

        # broadcast from a set-internal root (global rank id)
        root = 2 if r % 2 == 0 else 1
        b = np.full((3,), float(r), np.float32)
        bres = np.asarray(hvt.broadcast(b, root_rank=root, name="psb",
                                        process_set=mine))
        np.testing.assert_allclose(bres, float(root))

        # uneven allgather within the set (rows by set position)
        rows = (r // 2) + 1 if r % 2 == 0 else (r // 2) + 2
        g = np.full((rows, 2), float(r), np.float32)
        gres = np.asarray(hvt.allgather(g, name="psg", process_set=mine))
        if r % 2 == 0:
            assert gres.shape == (3, 2)   # ranks 0 (1 row) + 2 (2 rows)
            np.testing.assert_allclose(gres[:1], 0.0)
            np.testing.assert_allclose(gres[1:], 2.0)
        else:
            assert gres.shape == (5, 2)   # ranks 1 (2 rows) + 3 (3 rows)
            np.testing.assert_allclose(gres[:2], 1.0)
            np.testing.assert_allclose(gres[2:], 3.0)

        # non-member call is a loud local error
        other = odds if r % 2 == 0 else evens
        try:
            hvt.allreduce(x, name="bad", process_set=other)
            raise SystemExit("expected ValueError for non-member")
        except ValueError as e:
            assert "not in process set" in str(e)
        print(f"PS-OK-{r}", flush=True)
    """, np=4)
    for i in range(4):
        assert f"PS-OK-{i}" in out


def test_process_set_mismatch_errors_4proc():
    """Ranks disagreeing on a tensor's process set get a per-tensor
    ERROR (consistency check), not a hang. Sets [0,1,2] vs [1,2,3]
    overlap, so neither negotiation can ever complete — the conflict
    check must fire deterministically."""
    run_workers("""
        from horovod_tpu.common.process_sets import ProcessSet
        ps = ProcessSet([0, 1, 2]) if r < 2 else ProcessSet([1, 2, 3])
        try:
            hvt.allreduce(np.ones((2,), np.float32), name="mm",
                          process_set=ps)
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "process set" in str(e), e
    """, np=4)


def test_process_set_conflict_spares_disjoint_set_5proc():
    """A cross-set conflict errors exactly its participants; a disjoint
    set legitimately reusing the tensor name completes normally."""
    out = run_workers("""
        from horovod_tpu.common.process_sets import ProcessSet
        if r == 0:
            ps = ProcessSet([0, 1])
        elif r == 1:
            ps = ProcessSet([1, 2])
        elif r == 2:
            ps = ProcessSet([0, 2])
        else:
            ps = ProcessSet([3, 4])
        if r < 3:
            try:
                hvt.allreduce(np.ones((2,), np.float32), name="t",
                              process_set=ps)
                raise SystemExit("expected ValueError")
            except ValueError as e:
                assert "conflicting process sets" in str(e), e
        else:
            res = np.asarray(hvt.allreduce(
                np.full((2,), float(r), np.float32), op=hvt.Sum,
                name="t", process_set=ps))
            np.testing.assert_allclose(res, 7.0)  # 3 + 4
        print(f"SPARE-OK-{r}", flush=True)
    """, np=5)
    for i in range(5):
        assert f"SPARE-OK-{i}" in out


def test_intra_set_error_spares_disjoint_set_4proc():
    """A consistency ERROR inside one process set (shape mismatch) must
    be member-targeted: a disjoint set reusing the name completes with
    correct data — regression for the untargeted-ERROR corruption."""
    out = run_workers("""
        import time
        from horovod_tpu.common.process_sets import ProcessSet
        if r < 2:
            ps = ProcessSet([0, 1])
            try:
                # shapes differ across ranks 0/1 → per-tensor ERROR
                hvt.allreduce(np.zeros((r + 2,), np.float32), name="t",
                              process_set=ps)
                raise SystemExit("expected ValueError")
            except ValueError as e:
                assert "mismatched shape" in str(e), e
        else:
            ps = ProcessSet([2, 3])
            if r == 3:
                time.sleep(0.3)   # straggler: entry pends while the
                                  # other set errors
            res = np.asarray(hvt.allreduce(
                np.full((2,), float(r), np.float32), op=hvt.Sum,
                name="t", process_set=ps))
            np.testing.assert_allclose(res, 5.0)  # 2 + 3, NOT zeroed
        print(f"SPARED-{r}", flush=True)
    """, np=4)
    for i in range(4):
        assert f"SPARED-{i}" in out


def test_grouped_conflicted_process_set_errors_not_hangs_4proc():
    """A fusion group containing a tensor with conflicting process sets
    must dissolve with errors on every member, not hold siblings
    forever."""
    run_workers("""
        from horovod_tpu.common.process_sets import ProcessSet
        ps = ProcessSet([0, 1, 2]) if r < 2 else ProcessSet([1, 2, 3])
        try:
            hvt.grouped_allreduce(
                [np.ones((2,), np.float32), np.ones((3,), np.float32)],
                op=hvt.Sum, name="gg", process_set=ps)
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "process set" in str(e) or "aborted" in str(e), e
    """, np=4, timeout=60)


def test_tf_binding_tape_and_optimizer_2proc():
    """The TF binding's gradient plumbing over the real engine: tape
    gradients average across ranks; the optimizer wrapper applies reduced
    grads (numpy fakes stand in for tf objects — TF absent in image)."""
    out = run_workers("""
        import horovod_tpu.tensorflow as hvt_tf

        class FakeTape:
            def gradient(self, target, sources, output_gradients=None):
                return [np.full((4,), float(r + 1), np.float32), None]

        tape = hvt_tf.DistributedGradientTape(FakeTape())
        g0, g1 = tape.gradient("loss", ["w", "b"])
        np.testing.assert_allclose(np.asarray(g0), (1 + n) / 2.0)
        assert g1 is None

        class FakeOpt:
            applied = []
            def apply_gradients(self, gv, **kw):
                self.applied.append(list(gv))

        opt = hvt_tf.DistributedOptimizer(FakeOpt(),
                                          backward_passes_per_step=2)
        gr = np.full((3,), float(r), np.float32)
        assert opt.apply_gradients([(gr, "v")]) is None
        opt.apply_gradients([(gr, "v")])
        (applied,) = FakeOpt.applied
        # local sum over 2 passes, then cross-rank average: 2*mean(ranks)
        np.testing.assert_allclose(applied[0][0], 2 * (0 + 1) / 2.0)
        print(f"TF-OK-{r}", flush=True)
    """)
    assert "TF-OK-0" in out and "TF-OK-1" in out


def test_tf_real_tape_2proc():
    """Real tf.GradientTape through DistributedGradientTape over the
    engine: gradients average across ranks (requires tensorflow)."""
    import importlib.util

    if importlib.util.find_spec("tensorflow") is None:
        import pytest

        pytest.skip("tensorflow not installed")
    out = run_workers("""
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvt_tf

        w = tf.Variable([1.0, 2.0])
        with hvt_tf.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = tf.reduce_sum(w * w) * float(r + 1)
        (g,) = tape.gradient(loss, [w])
        # local grad = 2w(r+1); average over ranks = 2w * mean(r+1)
        np.testing.assert_allclose(
            np.asarray(g), 2 * np.array([1.0, 2.0]) * (1 + n) / 2.0,
            rtol=1e-6)
        print(f"TFREAL-OK-{r}", flush=True)
    """, timeout=180)
    assert "TFREAL-OK-0" in out and "TFREAL-OK-1" in out


def test_tf_sync_batch_norm_global_stats_2proc():
    """TF SyncBatchNormalization over the engine: each rank's
    normalization must use the GLOBAL batch statistics (requires
    tensorflow)."""
    import importlib.util

    if importlib.util.find_spec("tensorflow") is None:
        import pytest

        pytest.skip("tensorflow not installed")
    out = run_workers("""
        import tensorflow as tf
        import horovod_tpu.tensorflow as hvt_tf

        # UNEVEN batches: rank 0 has 2 rows of 0, rank 1 has 6 rows of
        # 8 → count-weighted global mean 6, var = 48/8·... E[x²]=48 →
        # var = 48 - 36 = 12 (equal-weight averaging would give mean 4)
        rows = 2 if r == 0 else 6
        x = tf.constant(np.full((rows, 3), float(r * 8), np.float32))
        bn = hvt_tf.SyncBatchNormalization(epsilon=1e-5)
        y = bn(x, training=True)
        expect = (r * 8 - 6.0) / np.sqrt(12.0 + 1e-5)
        np.testing.assert_allclose(y.numpy(), expect, rtol=1e-4)
        print(f"SBN-OK-{r}", flush=True)
    """, timeout=180)
    assert "SBN-OK-0" in out and "SBN-OK-1" in out


def test_sparse_allreduce_unequal_nnz_2proc():
    """Regression: average must divide by world size on every rank even
    when ranks contribute different row counts (allgatherv)."""
    out = run_workers("""
        from horovod_tpu.ops.sparse import sparse_allreduce
        if r == 0:
            idx = np.array([0], np.int32)
            vals = np.full((1, 2), 10.0, np.float32)
        else:
            idx = np.array([1, 2, 3], np.int32)
            vals = np.full((3, 2), 20.0, np.float32)
        gi, gv = sparse_allreduce(idx, vals, average=True, name="uneq")
        gi, gv = np.asarray(gi), np.asarray(gv)
        assert gi.shape[0] == 4
        np.testing.assert_allclose(gv[gi == 0], 5.0)
        np.testing.assert_allclose(gv[gi == 2], 10.0)
        print(f"UNEQ-OK-{r}", flush=True)
    """)
    assert "UNEQ-OK-0" in out and "UNEQ-OK-1" in out


def test_stall_inspector_warns_then_recovers_2proc():
    """Rank-0 stall watchdog (reference stall_inspector.h:30-96 /
    test_stall.py intent): when one rank lags on a tensor past
    HVT_STALL_WARN_SEC, rank 0 logs which ranks are missing; the
    collective still completes once the laggard submits."""
    out = run_workers("""
        import time
        if r == 1:
            time.sleep(2.5)   # rank 0 announces; rank 1 lags past warn
        res = np.asarray(hvt.allreduce(
            np.full((3,), float(r + 1), np.float32), name="laggy"))
        np.testing.assert_allclose(res, (1 + n) / 2.0)
    """, launcher_args=("--stall-warning-sec", "1"))
    assert "laggy" in out and "possible stall" in out, out[-2000:]
    assert "not by ranks [ 1 ]" in out, out[-2000:]


def test_shm_allreduce_single_host_2proc():
    """Single-host jobs pick the shared-memory data plane for allreduce
    (backend priority list: shm → hierarchical → ring); results match the
    ring exactly across dtypes."""
    out = run_workers("""
        for dt in (np.float32, np.float64, np.int32, np.float16):
            x = (np.arange(7) * (r + 1)).astype(dt)
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum,
                                           name=f"shm.{dt.__name__}"))
            expected = sum((np.arange(7) * (i + 1)).astype(dt)
                           for i in range(n))
            np.testing.assert_allclose(res.astype(np.float64),
                                       expected.astype(np.float64))
        # average path (postscale applied after the backend)
        a = np.asarray(hvt.allreduce(np.full(5, float(r + 1), np.float32),
                                     name="shm.avg"))
        np.testing.assert_allclose(a, (1 + n) / 2.0)
        # full-world broadcast rides the shm plane too (root publishes
        # once; non-members path still uses the ring)
        b = np.asarray(hvt.broadcast(np.full(6, float(r * 7 + 3),
                                             np.float32),
                                     root_rank=1, name="shm.bc"))
        np.testing.assert_allclose(b, 10.0)
        big = np.arange(1 << 20, dtype=np.float32) * (r + 1)
        bb = np.asarray(hvt.broadcast(big, root_rank=0, name="shm.bcbig"))
        np.testing.assert_allclose(bb, np.arange(1 << 20,
                                                 dtype=np.float32))
        # scalar (0-d) allgather: one row per rank, not garbage
        s = np.asarray(hvt.allgather(np.float32(r + 0.5), name="shm.sc"))
        np.testing.assert_allclose(s, [i + 0.5 for i in range(n)])
        # uneven allgather rides shm (single-copy concat from slots)
        g = np.asarray(hvt.allgather(np.full((r + 2, 3), float(r),
                                             np.float32), name="shm.ag"))
        assert g.shape == (2 * n + 1, 3), g.shape
        np.testing.assert_allclose(g[:2], 0.0)
        np.testing.assert_allclose(g[2:], 1.0)
        # reducescatter rides the shm allreduce (engine slices locally)
        rs = np.asarray(hvt.reducescatter(
            (np.arange(8, dtype=np.float32) + r).reshape(4, 2),
            op=hvt.Sum, name="shm.rs"))
        full = sum((np.arange(8, dtype=np.float32) + i).reshape(4, 2)
                   for i in range(n))
        np.testing.assert_allclose(rs, full[r * 2:(r + 1) * 2])
        # uneven alltoall rides shm (direct slot addressing)
        payload = np.asarray([[float(r)], [float(r) + 10],
                              [float(r) + 10]], np.float32)
        out2, rsp = hvt.alltoall(payload, splits=[1, 2], name="shm.a2a")
        out2 = np.asarray(out2)
        if r == 0:
            assert list(rsp) == [1, 1]
            np.testing.assert_allclose(out2[:, 0], [0.0, 1.0])
        else:
            assert list(rsp) == [2, 2]
            np.testing.assert_allclose(out2[:, 0],
                                       [10.0, 10.0, 11.0, 11.0])
    """, extra_env={"HVT_LOG_LEVEL": "debug"})
    assert "shm local data plane up" in out, out[-2000:]
    assert "shm allreduce engaged" in out, out[-2000:]
    assert "shm broadcast engaged" in out, out[-2000:]
    assert "shm allgather engaged" in out, out[-2000:]
    assert "shm alltoall engaged" in out, out[-2000:]


def test_shm_disabled_falls_back_to_ring_2proc():
    out = run_workers("""
        res = np.asarray(hvt.allreduce(np.full(4, float(r + 1),
                                               np.float32), name="noshm"))
        np.testing.assert_allclose(res, (1 + n) / 2.0)
    """, extra_env={"HVT_LOG_LEVEL": "debug", "HVT_SHM_ALLREDUCE": "0"})
    assert "shm local data plane up" not in out, out[-2000:]


def test_shm_allreduce_4proc_grouped_and_large():
    """4 ranks through the shm plane: grouped fusion + a payload big
    enough to span chunk boundaries."""
    run_workers("""
        big = (np.arange(100003) % 97).astype(np.float32) + r
        res = np.asarray(hvt.allreduce(big, op=hvt.Sum, name="shm.big"))
        expected = sum((np.arange(100003) % 97).astype(np.float32) + i
                       for i in range(n))
        np.testing.assert_allclose(res, expected)
        outs = hvt.grouped_allreduce(
            [np.full(3, float(r), np.float32),
             np.full(2, float(10 * r), np.float32)], op=hvt.Sum,
            name="shm.grp")
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   sum(range(n)))
        np.testing.assert_allclose(np.asarray(outs[1]),
                                   10.0 * sum(range(n)))
    """, np=4)


_SHM_SUBSET_BODY = """
    from horovod_tpu.common.process_sets import ProcessSet
    evens, odds = ProcessSet([0, 2]), ProcessSet([1, 3])
    mine = evens if r % 2 == 0 else odds
    pos = mine.ranks.index(r)

    # subset allreduce (disjoint sets concurrent — distinct barrier cells)
    x = np.full((5,), float(r + 1), np.float32)
    res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="sshm.ar",
                                   process_set=mine))
    np.testing.assert_allclose(res, float(sum(i + 1 for i in mine.ranks)))

    # subset broadcast (root = global rank)
    b = np.asarray(hvt.broadcast(np.full(4, float(r), np.float32),
                                 root_rank=mine.ranks[1], name="sshm.bc",
                                 process_set=mine))
    np.testing.assert_allclose(b, float(mine.ranks[1]))

    # subset uneven allgather (rows by set position)
    g = np.asarray(hvt.allgather(np.full((pos + 1, 2), float(r),
                                         np.float32), name="sshm.ag",
                                 process_set=mine))
    assert g.shape == (3, 2), g.shape
    np.testing.assert_allclose(g[:1], float(mine.ranks[0]))
    np.testing.assert_allclose(g[1:], float(mine.ranks[1]))

    # subset uneven alltoall (splits by set position)
    payload = np.asarray([[float(10 * r)], [float(10 * r) + 1],
                          [float(10 * r) + 1]], np.float32)
    out2, rsp = hvt.alltoall(payload, splits=[1, 2], name="sshm.a2a",
                             process_set=mine)
    out2 = np.asarray(out2)
    peers = mine.ranks
    if pos == 0:
        assert list(rsp) == [1, 1], rsp
        np.testing.assert_allclose(out2[:, 0],
                                   [10.0 * peers[0], 10.0 * peers[1]])
    else:
        assert list(rsp) == [2, 2], rsp
        np.testing.assert_allclose(
            out2[:, 0], [10.0 * peers[0] + 1, 10.0 * peers[0] + 1,
                         10.0 * peers[1] + 1, 10.0 * peers[1] + 1])

    # subset reducescatter (native chunk reduce on the shm plane)
    rs = np.asarray(hvt.reducescatter(
        (np.arange(8, dtype=np.float32) + r).reshape(4, 2), op=hvt.Sum,
        name="sshm.rs", process_set=mine))
    full = sum((np.arange(8, dtype=np.float32) + i).reshape(4, 2)
               for i in mine.ranks)
    np.testing.assert_allclose(rs, full[pos * 2:(pos + 1) * 2])

    # full-world reducescatter also runs the native chunk path
    rsw = np.asarray(hvt.reducescatter(
        (np.arange(8, dtype=np.float32) * (r + 1)).reshape(4, 2),
        op=hvt.Sum, name="sshm.rsw"))
    fullw = sum((np.arange(8, dtype=np.float32) * (i + 1)).reshape(4, 2)
                for i in range(n))
    np.testing.assert_allclose(rsw, fullw[r:r + 1])
"""


def test_shm_serves_subsets_and_native_reducescatter_4proc():
    """Process-subset collectives and reduce-scatter ride the shm plane
    (VERDICT r2 #6; reference operation_manager.cc serves every op from
    the selected backend): per-group barrier cells, direct slot reads,
    native chunk reduce for reducescatter."""
    out = run_workers(_SHM_SUBSET_BODY, np=4,
                      extra_env={"HVT_LOG_LEVEL": "debug"})
    assert "shm local data plane up" in out, out[-2000:]
    assert "shm subset collective engaged" in out, out[-2000:]
    assert "shm reducescatter engaged (native chunk" in out, out[-2000:]


def test_subset_collectives_identical_without_shm_4proc():
    """Same program with the shm plane disabled: the ring group paths must
    produce identical results (backend choice is invisible to callers)."""
    out = run_workers(_SHM_SUBSET_BODY, np=4,
                      extra_env={"HVT_LOG_LEVEL": "debug",
                                 "HVT_SHM_ALLREDUCE": "0"})
    assert "shm local data plane up" not in out, out[-2000:]


def test_shm_subset_full_world_interleaved_4proc():
    """Stress the progress-word barrier: odd ranks skip the even-subset
    response and run ahead into the next full-world collective while the
    subset is still in flight — a shared-counter barrier would be
    polluted (premature release / lost arrivals); progress words keyed
    to the global response sequence stay sound."""
    run_workers("""
        from horovod_tpu.common.process_sets import ProcessSet
        evens = ProcessSet([0, 2])
        for i in range(30):
            if r % 2 == 0:
                x = np.full((257,), float(r + i), np.float32)
                res = np.asarray(hvt.allreduce(x, op=hvt.Sum,
                                               name=f"il.e.{i}",
                                               process_set=evens))
                np.testing.assert_allclose(res, 2.0 * i + 2.0)
            w = np.asarray(hvt.allreduce(
                np.full((64,), float(r + 1), np.float32), op=hvt.Sum,
                name=f"il.w.{i}"))
            np.testing.assert_allclose(
                w, float(sum(k + 1 for k in range(n))))
    """, np=4, extra_env={"HVT_LOG_LEVEL": "debug"})
