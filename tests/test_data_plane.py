"""Gang tests for the eager data plane: event-driven cycle draining
(small-tensor latency well under ``cycle_ms``), the pipelined chunked
ring's numerics at chunk-boundary sizes across dtypes/ReduceKinds, and
the negotiated wire-codec family (bf16/int8/fp8 tolerance, exact wire
byte counters, cross-rank bit-identity per codec, chunk/block boundary
decode, error feedback, topology-aware {intra, inter} selection on the
PR 6 lane machinery, default-off exactness).

Every test launches a real multi-process gang through hvtrun on
loopback, with ``HVT_SHM_ALLREDUCE=0`` so the TCP ring — the code under
test — serves the collectives.
"""

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

_PORT = [24000 + (os.getpid() * 613) % 10000]


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


def run_workers(body, np=2, timeout=120, extra_env=None, pre=""):
    """Launch an np-proc gang running `body` after hvt.init(). `pre`
    runs BEFORE init — e.g. setting a per-rank HVT_TOPO_HOST off
    HVT_PROCESS_ID to fake a multi-host layout on loopback."""
    _next_port()
    script = textwrap.dedent(f"""
        import os, sys, time, zlib
        sys.path.insert(0, {REPO!r})
        import numpy as np
        {textwrap.indent(textwrap.dedent(pre), '        ').strip() or 'pass'}
        import horovod_tpu as hvt
        hvt.init()
        r, n = hvt.rank(), hvt.size()
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print(f"WORKER-{{r}}-DONE", flush=True)
        hvt.shutdown()
    """)
    path = f"/tmp/hvt_dptest_{os.getpid()}_{_PORT[0]}.py"
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                "HVT_SHM_ALLREDUCE": "0"})
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", str(np),
         "--master-port", str(_PORT[0]), sys.executable, path],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"rc={proc.returncode}\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    out = proc.stdout + proc.stderr
    for i in range(np):
        assert f"WORKER-{i}-DONE" in out
    return out


def test_event_driven_drains_back_to_back():
    """With cycle_ms cranked to 200, a sleep-paced loop needs ≥ one full
    sleep per op (10 hot ops ≥ 2 s); the event-driven loop must clear
    all 10 in a fraction of that. Also pins the observability satellite:
    WAKEUP events in the ring and both new histograms populated."""
    out = run_workers("""
        from horovod_tpu.engine import native
        x = np.arange(1024, dtype=np.float32)
        hvt.allreduce(x, op=hvt.Sum, name="hot")  # prime the cache
        t0 = time.perf_counter()
        for _ in range(10):
            hvt.allreduce(x, op=hvt.Sum, name="hot")
        elapsed = time.perf_counter() - t0
        assert elapsed < 1.0, f"10 hot 4KB ops took {elapsed:.2f}s " \
            "with cycle_ms=200 — event-driven draining is not engaging"
        st = native.engine_stats()
        assert st["wakeup_hist"]["count"] > 0, "no wakeups observed"
        assert st["cycle_hist"]["count"] > 0, "no cycle durations"
        kinds = {e["kind_name"] for e in native.drain_events()}
        assert "WAKEUP" in kinds, f"no WAKEUP events (saw {kinds})"
        if r == 0:
            print("ELAPSED", round(elapsed, 3), flush=True)
    """, extra_env={"HVT_CYCLE_TIME_MS": "200"})
    assert "ELAPSED" in out


def test_pipelined_ring_numerics_at_chunk_boundaries():
    """Chunk size forced to 4 KB (1024 fp32 elems) so payloads cross
    chunk boundaries: below, at, just past, several-chunks+remainder,
    and count < ranks. All dtypes, all elementwise ReduceKinds."""
    run_workers("""
        sizes = [1, 2, 3, 1023, 1024, 1025, 4103]
        dtypes = [np.float32, np.float64, np.float16, np.int32,
                  np.int64, np.uint8, np.int8]
        try:
            import ml_dtypes
            dtypes.append(np.dtype("bfloat16"))
        except Exception:
            pass
        for numel in sizes:
            for dt in dtypes:
                base = (np.arange(numel) % 5 + 1)
                x = (base + r).astype(dt)
                nm = f"s.{numel}.{np.dtype(dt).name}"
                res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=nm))
                exp = sum((base + i).astype(dt) for i in range(n))
                np.testing.assert_array_equal(
                    res.astype(np.float64), exp.astype(np.float64),
                    err_msg=nm)
        # other ReduceKinds at a boundary-crossing size
        numel = 1025
        base = np.arange(numel) % 7 + 1
        for op, fn in ((hvt.Min, np.minimum), (hvt.Max, np.maximum)):
            x = ((base + 11 * r) % 13).astype(np.float32)
            res = np.asarray(hvt.allreduce(x, op=op, name=f"mm.{op.name}"))
            exp = ((base + 0) % 13).astype(np.float32)
            for i in range(1, n):
                exp = fn(exp, ((base + 11 * i) % 13).astype(np.float32))
            np.testing.assert_array_equal(res, exp)
        x = np.where(base % 2 == 0, 2.0, 1.0).astype(np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Product, name="prod"))
        np.testing.assert_array_equal(res, x ** n)
        # Average exercises the postscale fold (scale rides the ring's
        # allgather pass); ints now round rather than truncate
        x = np.full((numel,), float(r + 1), np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Average, name="avgf"))
        np.testing.assert_allclose(res, (1 + n) / 2.0)
        xi = np.full((numel,), r + 1, np.int32)
        res = np.asarray(hvt.allreduce(xi, op=hvt.Average, name="avgi"))
        # llround semantics: positive halves round AWAY from zero
        exp_avg = int(np.floor((n * (n + 1) / 2) / n + 0.5))
        np.testing.assert_array_equal(res, exp_avg)
    """, extra_env={"HVT_RING_CHUNK_BYTES": "4096"}, timeout=180)


def test_bf16_wire_allreduce_4proc():
    """HVT_WIRE_COMPRESSION=bf16 on a 4-proc gang: fp32 results within
    bf16 tolerance, bit-identical across ranks, and exactly half the
    raw plane's wire bytes (counted by the per-op tx counters)."""
    run_workers("""
        from horovod_tpu.engine import native
        assert hvt.wire_compression() == ("bf16", "bf16")
        numel = 1 << 16
        x = (np.arange(numel, dtype=np.float32) % 997) * 0.123 + r
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="c"))
        exp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.123 + i
                  for i in range(n))
        # documented tolerance: bf16 has an 8-bit mantissa → relative
        # error ≤ ~2^-7 per wire hop (docs/performance.md)
        np.testing.assert_allclose(res, exp, rtol=1e-2)
        st = native.engine_stats()
        tx = st["wire_tx_bytes"]["allreduce"]
        txc = st["wire_tx_comp_bytes"]["allreduce"]
        # ring sends 2(n-1)/n of the payload per rank; compressed form
        # halves it, and every allreduce byte went out compressed
        raw_wire = 2 * (n - 1) * numel * 4 // n
        assert tx == raw_wire // 2, (tx, raw_wire)
        assert txc == tx > 0
        # all ranks end bit-identical (owners round-trip through bf16)
        crcs = hvt.allgather(
            np.array([zlib.crc32(res.tobytes())], np.int64), name="crc")
        assert len(set(int(c) for c in np.asarray(crcs))) == 1
    """, np=4, extra_env={"HVT_WIRE_COMPRESSION": "bf16"}, timeout=180)


def test_wire_default_off_exact_and_uncompressed():
    """Without HVT_WIRE_COMPRESSION the plane must be bit-exact (integer
    payloads sum exactly in fp32) and count zero compressed bytes."""
    run_workers("""
        from horovod_tpu.engine import native
        assert hvt.wire_compression() == ("none", "none")
        numel = 1 << 16
        x = (np.arange(numel) % 1001 + r).astype(np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="exact"))
        exp = sum((np.arange(numel) % 1001 + i).astype(np.float32)
                  for i in range(n))
        np.testing.assert_array_equal(res, exp)
        st = native.engine_stats()
        assert st["wire_tx_comp_bytes"]["allreduce"] == 0
        assert st["wire_tx_bytes"]["allreduce"] == \
            2 * (n - 1) * numel * 4 // n
    """)


# Per-256-elem block: 4-byte in-band scale + 1 byte per elem.
_BLOCK_WIRE = "lambda n: (n // 256) * 260 + (4 + n % 256 if n % 256 else 0)"


def test_block_codec_crc_identity_and_exact_bytes_4proc():
    """int8/fp8 on a 4-proc ring: results within the documented block
    tolerance, bit-identical across ranks (owner roundtrip), and the
    per-op + per-codec tx counters equal to the EXACT wire formula —
    ≥3.5x under raw for int8 (the r09 headline)."""
    for codec in ("int8", "fp8"):
        out = run_workers(f"""
            from horovod_tpu.engine import native
            codec = {codec!r}
            assert hvt.wire_compression() == (codec, codec)
            numel = 1 << 16
            x = (np.arange(numel, dtype=np.float32) % 997) * 0.123 + r
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="c"))
            exp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.123
                      + i for i in range(n))
            # block-scaled error: ~blockmax/254 (int8) / ~blockmax/16
            # (fp8) per quantization, a few quantizations deep
            np.testing.assert_allclose(
                res, exp, rtol=0.02 if codec == "int8" else 0.2,
                atol=np.abs(exp).max() * 0.02)
            st = native.engine_stats()
            tx = st["wire_tx_bytes"]["allreduce"]
            seg = numel // n
            wire = {_BLOCK_WIRE}
            # 2(n-1) segments per rank, each compressed independently
            assert tx == 2 * (n - 1) * wire(seg), (tx, wire(seg))
            assert st["wire_tx_comp_bytes"]["allreduce"] == tx
            assert st["codec_tx_bytes"][codec]["allreduce"] == tx
            raw = 2 * (n - 1) * seg * 4
            if codec == "int8":
                assert raw / tx >= 3.5, (raw, tx)
            crcs = hvt.allgather(
                np.array([zlib.crc32(res.tobytes())], np.int64),
                name="crc")
            assert len(set(int(c) for c in np.asarray(crcs))) == 1
        """, np=4, extra_env={"HVT_WIRE_COMPRESSION": codec},
            timeout=180)
        assert "WORKER-3-DONE" in out


def test_block_codec_chunk_boundary_numerics():
    """HVT_RING_CHUNK_BYTES=4096 forces blocks to straddle pipeline
    chunk edges (a 260-byte wire block never divides 4096): sizes
    below/at/past block and chunk boundaries must decode identically to
    the unchunked path, non-fp32 dtypes must stay exact (codecs gate on
    fp32), and Average must ride the postscale fold."""
    run_workers("""
        from horovod_tpu.engine import native
        sizes = [1, 255, 256, 257, 1023, 1024, 1025, 4103, 16384]
        for numel in sizes:
            x = ((np.arange(numel) % 997) * 0.37 + r).astype(np.float32)
            nm = f"cb.{numel}"
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=nm))
            exp = sum(((np.arange(numel) % 997) * 0.37 + i)
                      .astype(np.float32) for i in range(n))
            np.testing.assert_allclose(res, exp, rtol=0.02,
                                       atol=np.abs(exp).max() * 0.02,
                                       err_msg=nm)
        # non-fp32 payloads move raw and stay EXACT under the codec env
        for dt in (np.int32, np.float64, np.float16):
            numel = 1025
            x = (np.arange(numel) % 5 + 1 + r).astype(dt)
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum,
                                           name=f"ex.{np.dtype(dt).name}"))
            exp = sum((np.arange(numel) % 5 + 1 + i).astype(dt)
                      for i in range(n))
            np.testing.assert_array_equal(res.astype(np.float64),
                                          exp.astype(np.float64))
        # Average: postscale folds in before the owner roundtrip
        x = np.full((4103,), float(r + 1), np.float32)
        res = np.asarray(hvt.allreduce(x, op=hvt.Average, name="avg"))
        np.testing.assert_allclose(res, (1 + n) / 2.0, rtol=0.01)
    """, extra_env={"HVT_RING_CHUNK_BYTES": "4096",
                    "HVT_WIRE_COMPRESSION": "int8"}, timeout=180)


def test_error_feedback_unbiases_repeated_allreduce():
    """Repeated int8 allreduce-average of a constant tensor whose small
    entries sit far below the block quantization threshold: without EF
    they are zeroed every step (running mean stays 0); with EF the
    residual carries until it crosses the threshold and the running
    mean converges to the exact average."""
    for ef, expect_biased in (("1", False), ("0", True)):
        out = run_workers("""
            from horovod_tpu.engine import native
            x = np.full(256, 0.01, np.float32)
            x[0] = 100.0  # pins the block scale at ~0.79 >> 0.01
            steps = 120
            acc = np.zeros(256)
            for t in range(steps):
                acc += np.asarray(
                    hvt.allreduce(x, op=hvt.Average, name="ef"))
            mean = acc / steps
            st = native.engine_stats()
            if r == 0:
                print("EF-RESULT", mean[1], mean[0],
                      st["ef_residual_bytes"], flush=True)
        """, extra_env={"HVT_WIRE_COMPRESSION": "int8",
                        "HVT_ERROR_FEEDBACK": ef}, timeout=240)
        line = [ln for ln in out.splitlines() if "EF-RESULT" in ln][0]
        small, big, ef_bytes = line.split("EF-RESULT", 1)[1].split()
        small, big = float(small), float(big)
        assert abs(big - 100.0) < 0.5
        if expect_biased:
            assert small == 0.0, f"no-EF mean should be zeroed: {small}"
            assert int(ef_bytes) == 0
        else:
            assert abs(small - 0.01) < 0.005, \
                f"EF mean should approach 0.01: {small}"
            assert int(ef_bytes) >= 256 * 4


_FAKE_2HOSTS = """
import os
os.environ["HVT_TOPO_HOST"] = (
    "hostA" if int(os.environ.get("HVT_PROCESS_ID", "0")) < 2 else "hostB")
"""


def test_topology_pair_mixed_lanes():
    """EQuARX selection on the PR 6 lane machinery: with the pair
    `none,int8` on a faked 2x2-host layout, a same-host lane moves raw
    bytes (exact results) while a cross-host lane moves int8 — two
    lanes, two codecs, one gang. The global allreduce rides the
    hierarchical backend (intra phases raw, cross phase int8)."""
    run_workers("""
        from horovod_tpu.engine import native
        from horovod_tpu.common.process_sets import (ProcessSet,
                                                     add_process_set)
        assert hvt.wire_compression() == ("none", "int8")
        intra_set = add_process_set(ProcessSet([0, 1]))   # one host
        inter_set = add_process_set(ProcessSet([0, 2]))   # spans hosts
        numel = 1 << 12
        base = (np.arange(numel) % 997).astype(np.float32) * 0.61
        # same-host lane: intra codec "none" → bit-exact
        if r in (0, 1):
            res = np.asarray(hvt.allreduce(base + r, op=hvt.Sum,
                                           name="laneA",
                                           process_set=intra_set))
            np.testing.assert_array_equal(res, (base + 0) + (base + 1))
        # cross-host lane: inter codec int8 → lossy but close, and the
        # int8 tx counter moves on its members
        if r in (0, 2):
            res = np.asarray(hvt.allreduce(base + r, op=hvt.Sum,
                                           name="laneB",
                                           process_set=inter_set))
            exp = (base + 0) + (base + 2)
            np.testing.assert_allclose(res, exp, rtol=0.02,
                                       atol=np.abs(exp).max() * 0.02)
            assert not np.array_equal(res, exp), \
                "cross-host lane should be quantized"
        # global allreduce: hierarchical (2 hosts x 2 ranks) — works and
        # stays within int8 tolerance (cross phase only is lossy)
        res = np.asarray(hvt.allreduce(base + r, op=hvt.Sum, name="g"))
        exp = sum(base + i for i in range(n))
        np.testing.assert_allclose(res, exp, rtol=0.02,
                                   atol=np.abs(exp).max() * 0.02)
        st = native.engine_stats()
        ctx = st["codec_tx_bytes"]
        if r in (0, 2):
            assert ctx["int8"]["allreduce"] > 0, ctx
        assert ctx["none"]["allreduce"] > 0, ctx
        # cross-gang agreement on the pair even though only rank 0's
        # stamps matter
        crcs = hvt.allgather(np.array([zlib.crc32(res.tobytes())],
                                      np.int64), name="crcg")
        assert len(set(int(c) for c in np.asarray(crcs))) == 1
    """, np=4, pre=_FAKE_2HOSTS,
        extra_env={"HVT_WIRE_COMPRESSION": "none,int8"}, timeout=240)


def test_auto_mode_explores_and_converges():
    """HVT_WIRE_COMPRESSION=auto on a faked 2-host pair (auto quantizes
    only inter-host links, so a genuinely single-host gang correctly
    stays raw): rank 0's tuner rotates raw/bf16/int8 on live traffic
    (several codecs' tx counters move during exploration), results stay
    within the loosest candidate's tolerance, and the gang never
    wedges."""
    run_workers("""
        from horovod_tpu.engine import native
        numel = 1 << 14
        x = (np.arange(numel, dtype=np.float32) % 997) * 0.5 + r
        exp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.5 + i
                  for i in range(n))
        for t in range(30):
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="auto"))
            np.testing.assert_allclose(res, exp, rtol=0.02,
                                       atol=np.abs(exp).max() * 0.02)
        intra, inter, auto = native.wire_compression()
        assert auto and intra == 0
        st = native.engine_stats()
        moved = [c for c, ops in st["codec_tx_bytes"].items()
                 if ops["allreduce"] > 0]
        assert len(moved) >= 2, f"tuner never explored: {moved}"
    """, pre="""
        import os
        os.environ["HVT_TOPO_HOST"] = \
            "h" + os.environ.get("HVT_PROCESS_ID", "0")
    """, extra_env={"HVT_WIRE_COMPRESSION": "auto"}, timeout=240)


def test_auto_mode_single_host_stays_raw():
    """auto on a genuinely single-host gang: no group has an inter-host
    hop, so the tuner must never be consulted — the stamped/reported
    inter codec stays raw at every step (a rotating exploration pick
    here would report phantom codecs and break bypass uniformity),
    results are bit-exact, and only the `none` tx counter moves."""
    run_workers("""
        from horovod_tpu.engine import native
        numel = 1 << 12
        x = (np.arange(numel, dtype=np.float32) % 997) * 0.5 + r
        exp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.5 + i
                  for i in range(n))
        for t in range(20):
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="raw1h"))
            np.testing.assert_array_equal(res, exp)
            intra, inter, auto = native.wire_compression()
            assert auto and intra == 0 and inter == 0, \\
                (t, intra, inter, auto)
        st = native.engine_stats()
        moved = [c for c, ops in st["codec_tx_bytes"].items()
                 if ops["allreduce"] > 0]
        assert moved == ["none"], moved
    """, extra_env={"HVT_WIRE_COMPRESSION": "auto"}, timeout=240)


def test_auto_mode_mixed_workload_keeps_bypass():
    """auto on a faked 2-host gang with a MIXED per-step workload: a
    single-host process-set allreduce (link intra, inter pick forced
    raw) co-scheduled with a global cross-host allreduce (tuner-picked
    inter). The intra-only response's forced-raw stamp sits outside the
    bypass uniformity accounting — while the tuner explores nonzero
    codecs (trials 6..15 are deterministically bf16/int8), the
    steady-state positions-form bypass must still engage. Async submits
    put both announces in one control frame per rank, so the two
    responses land in the same cycle by construction (the root ingests
    exactly one frame per child per cycle)."""
    run_workers("""
        from horovod_tpu.engine import native
        from horovod_tpu.common.process_sets import (ProcessSet,
                                                     add_process_set)
        lane = add_process_set(ProcessSet([0, 1]))  # hostA only
        numel = 1 << 12
        x = (np.arange(numel, dtype=np.float32) % 997) * 0.5 + r
        gexp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.5 + i
                   for i in range(n))
        lexp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.5 + i
                   for i in range(2))

        def step():
            hs = []
            if r in (0, 1):
                hs.append(("lane", hvt.allreduce_async(
                    x, op=hvt.Sum, name="mlane", process_set=lane)))
            hs.append(("g", hvt.allreduce_async(x, op=hvt.Sum,
                                                name="mglob")))
            for kind, h in hs:
                res = np.asarray(h.wait())
                if kind == "lane":  # intra link stays raw → bit-exact
                    np.testing.assert_array_equal(res, lexp)
                else:  # rotating inter codec → loosest-candidate tol
                    np.testing.assert_allclose(
                        res, gexp, rtol=0.02,
                        atol=np.abs(gexp).max() * 0.02)

        for t in range(6):   # cache warm + the 5 raw-trial steps
            step()
        b0 = native.engine_stats()["ctrl_bypass_cycles"]
        for t in range(10):  # bf16/int8 exploration: picks nonzero
            step()
        delta = native.engine_stats()["ctrl_bypass_cycles"] - b0
        assert delta >= 6, \\
            f"mixed cycles stopped bypassing under auto: delta={delta}"
    """, np=4, pre=_FAKE_2HOSTS,
        extra_env={"HVT_WIRE_COMPRESSION": "auto"}, timeout=240)


def test_pair_spec_intra_codec_with_auto_inter():
    """`bf16,auto` honors the configured intra codec: on a single-host
    gang the in-host links actually move bf16 (tx counter proves it,
    and the stamped pair reports it) while the auto inter side stays
    raw for lack of inter-host hops."""
    run_workers("""
        from horovod_tpu.engine import native
        numel = 1 << 12
        x = (np.arange(numel, dtype=np.float32) % 997) * 0.5 + r
        exp = sum((np.arange(numel, dtype=np.float32) % 997) * 0.5 + i
                  for i in range(n))
        for t in range(10):
            res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name="bfa"))
            np.testing.assert_allclose(res, exp, rtol=0.01,
                                       atol=np.abs(exp).max() * 0.01)
        intra, inter, auto = native.wire_compression()
        assert auto and intra == 1 and inter == 0, (intra, inter, auto)
        st = native.engine_stats()
        assert st["codec_tx_bytes"]["bf16"]["allreduce"] > 0, \\
            st["codec_tx_bytes"]
    """, extra_env={"HVT_WIRE_COMPRESSION": "bf16,auto"}, timeout=240)
