#!/usr/bin/env python
"""Simulated fleet-telemetry scaling harness
(``python benchmarks/telemetry_scaling.py``).

Proves the leader-aggregated telemetry plane
(``horovod_tpu/metrics/telemetry.py``) at 64 simulated ranks on 8 fake
hosts, reusing the featherweight MiniEngine worker of
``benchmarks/ctrl_plane_scaling.py`` (bare ctypes over
``libhvt_core.so`` — no jax/numpy per worker; the ``horovod_tpu``
package root is stubbed so the import-light telemetry/metrics modules
load without pulling jax into 64 processes).

Each run spins up one REAL engine gang over loopback plus the real
driver-side ``RendezvousServer`` (with ``/statusz``), runs the real
:class:`TelemetryPusher` per rank in either mode, and measures:

- **driver-scraped telemetry bytes per push window** — the rendezvous
  store's server-side ingest accounting (``_Store.put_bytes``) over the
  ``debugz`` + ``telemetry`` scopes: ~64 per-rank snapshots/window
  direct vs ~8 merged host frames/window with leader aggregation. The
  committed claim (``benchmarks/r13_telemetry_scaling.json``) gates
  ≥4x reduction.
- **rollup equivalence** — ``/statusz`` covers the same 64 ranks in
  both modes, and in leader mode the merged
  ``hvt_ctrl_tx_bytes_total`` equals the per-rank compact-record sum
  exactly (counters sum-identical; the merge algebra on real data).
- **/statusz latency** (GET p50) and **clean-gang alerts** (the
  health-rule false-positive pin at 64 ranks).
- **hvt_top round-trip** — ``python -m horovod_tpu.tools.hvt_top
  --once --json`` against the live server must return the same
  schema-valid view (the ``ci.sh --obs`` assert).

Byte metrics are workload-determined, so the reduction claim is stable
on a loaded shared box; only the latency column is noisy and ``--check``
never gates on it (CHANGES.md PR 7 methodology).

Modes:
    --smoke [--out X.json]   8 ranks / 2 hosts pair (ci.sh --obs)
    --capture [--out ...]    the full 64-rank / 8-host r13 matrix
    --check X.json           artifact schema + claims validation
Worker mode is selected internally via HVT_TS_WORKER.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import threading
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build",
                   "libhvt_core.so")

SCHEMA = "hvt-telemetry-scale-r1"
MEASURED_SCOPES = ("debugz", "telemetry")


def _stub_package():
    """Register a bare ``horovod_tpu`` package root so submodule
    imports (``horovod_tpu.metrics.telemetry``,
    ``horovod_tpu.runner.http_server``) work WITHOUT executing the real
    package ``__init__`` — which imports jax, and 64 workers importing
    jax is exactly the weight this harness exists to avoid."""
    if "horovod_tpu" not in sys.modules:
        pkg = types.ModuleType("horovod_tpu")
        pkg.__path__ = [os.path.join(REPO, "horovod_tpu")]
        sys.modules["horovod_tpu"] = pkg
    if REPO not in sys.path:
        sys.path.insert(0, REPO)


def mini_diagnostics(eng):
    """``hvt_diagnostics`` over the MiniEngine's ctypes handle — the
    same JSON ``hvt.diagnostics()`` returns, without importing the
    numpy-backed bridge."""
    import ctypes

    lib = eng.lib
    lib.hvt_diagnostics.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.hvt_diagnostics.restype = ctypes.c_int
    n = int(lib.hvt_diagnostics(None, 0))
    buf = ctypes.create_string_buffer(n + 16)
    lib.hvt_diagnostics(buf, n + 16)
    try:
        return json.loads(buf.value.decode("utf-8", "replace"))
    except ValueError:
        return {"engine": {"running": True}}


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def make_snapshot_fn(eng, rank, telemetry_mod):
    def snapshot():
        diag = mini_diagnostics(eng)
        diag["process_rank"] = rank
        return telemetry_mod.build_snapshot(
            rank, telemetry_mod.host_name(), diag, eng.stats())
    return snapshot


def _worker():
    _stub_package()
    from benchmarks.ctrl_plane_scaling import MiniEngine
    from horovod_tpu.metrics import telemetry as T

    spec = json.loads(os.environ["HVT_TS_SPEC"])
    rank = int(os.environ["HVT_TS_RANK"])
    size = int(os.environ["HVT_TS_SIZE"])
    port = int(os.environ["HVT_TS_PORT"])
    kv = os.environ["HVT_TS_KV"]
    debug = os.environ.get("HVT_TS_DEBUG")

    def trace(msg):
        if debug:
            print(f"[ts r{rank}] {msg}", file=sys.stderr, flush=True)

    eng = MiniEngine()
    eng.init(rank, size, port=port, cycle_ms=spec.get("cycle_ms", 2))
    trace("engine up")
    numel = spec.get("numel", 64)
    values = [float(rank + 1)] * numel

    def barrier(tag):
        out = eng.allreduce(f"sync.{tag}", [1.0])
        assert int(out[0]) == size, (tag, out)
        trace(f"barrier {tag}")

    stop = threading.Event()
    pusher = T.TelemetryPusher(
        kv, rank, make_snapshot_fn(eng, rank, T), stop,
        period_sec=spec["interval_sec"])

    barrier("init")

    def loop():
        while True:
            pusher.step()
            if stop.wait(T.jittered(pusher.period_sec)):
                return

    th = threading.Thread(target=loop, daemon=True)
    th.start()

    # work phase: a light steady-state collective trickle — enough to
    # keep counters moving and negotiations real without saturating the
    # shared box (the telemetry plane, not the data plane, is under
    # test; ctrl_plane_scaling owns the data-plane load story).
    # DETERMINISTIC step count, never wall-clock bounded: with a
    # time-bounded loop one rank crosses the deadline an iteration
    # before the rest, stops submitting the shared names, and the other
    # N-1 wedge inside the allreduce while it waits at the barrier — a
    # name-desync deadlock the stall inspector reports but (correctly)
    # never aborts, because control traffic keeps the progress
    # deadlines re-armed. Found live at 64 ranks.
    tensors = spec.get("tensors", 4)
    step_sleep = spec.get("step_sleep", 0.25)
    steps = spec.get("steps") or max(
        1, int(spec["work_sec"] / max(step_sleep, 0.05)))
    # submit-side straggler injection (tests): rank `straggler_rank`
    # lags `straggler_sleep_sec` before each step's submissions — the
    # slow-host shape rank 0's arrival table actually sees. (An
    # engine-level delay_ms fault alone slows the GANG in lockstep:
    # the sleep sits between negotiation and the ring transfer, and a
    # ring collective is gang-synchronous, so no announce skew ever
    # reaches the arrival table — found live writing the acceptance
    # test.)
    lag = (spec.get("straggler_sleep_sec", 0.0)
           if spec.get("straggler_rank") == rank else 0.0)
    for _ in range(steps):
        if lag:
            time.sleep(lag)
        for j in range(tensors):
            eng.allreduce(f"s.{j:03d}.grad/layer_weight", values)
        time.sleep(step_sleep)
    barrier("work")

    # deterministic final pushes: counters are static after the barrier
    # (no submissions in flight; the idle heartbeat is 30 s away), so
    # the leader's merged counters can be checked sum-identical against
    # the per-rank records of the same frame.
    stop.set()
    th.join(timeout=10)
    if pusher.role != "leader":
        pusher.step()          # member → leader, or direct → server
    barrier("final_members")
    if pusher.role == "leader":
        pusher.step()          # fold members' final snaps, publish
    barrier("final_frames")
    if rank == 0:
        try:
            from horovod_tpu.runner.http_client import put_bytes
            put_bytes(kv, "/kv/ctl/done", b"1", timeout=5)
        except Exception:
            pass
        # hold the gang until the driver finishes its final reads (the
        # done/teardown handshake) so statusz latency is measured
        # against a live store
        deadline = time.monotonic() + spec.get("teardown_wait_sec", 30)
        from horovod_tpu.runner.http_client import get_json
        while time.monotonic() < deadline:
            try:
                if get_json(kv, "/kv/ctl/exit", timeout=2,
                            retries=0) is not None:
                    break
            except Exception:
                pass
            time.sleep(0.2)
    barrier("exit")
    pusher.close()
    eng.shutdown()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class _Slot:
    def __init__(self, hostname, rank, local_rank, local_size, size,
                 hosts):
        self.hostname = hostname
        self.rank = rank
        self.local_rank = local_rank
        self.local_size = local_size
        self.size = size
        self.cross_rank = int(hostname[1:]) if hostname[1:].isdigit() \
            else 0
        self.cross_size = hosts


def _get_json(addr, path, timeout=10):
    from horovod_tpu.runner.http_client import get_json

    return get_json(addr, path, timeout=timeout, retries=0)


def start_driver(np_, hosts):
    """RendezvousServer with /statusz, initialized with the fake
    host/slot layout. Returns (server, 'host:port')."""
    _stub_package()
    from horovod_tpu.runner.http_server import RendezvousServer

    per_host = max(1, np_ // hosts)
    slots = [_Slot(f"h{min(r // per_host, hosts - 1)}", r,
                   r % per_host, per_host, np_, hosts)
             for r in range(np_)]
    server = RendezvousServer()
    server.init(slots)
    port = server.start(0)
    return server, f"127.0.0.1:{port}"


def spawn_workers(np_, hosts, mode, spec, engine_port, kv_addr,
                  extra_env=None):
    """One featherweight worker process per rank; ranks pack
    contiguously onto `hosts` fake hosts. ``mode`` is ``direct`` or
    ``leader`` (leader = lowest rank of each host aggregates)."""
    per_host = max(1, np_ // hosts)
    procs = []
    for r in range(np_):
        host_i = min(r // per_host, hosts - 1)
        if mode == "leader":
            role = "leader" if r % per_host == 0 and r // per_host < hosts \
                else "member"
        else:
            role = "direct"
        env = dict(os.environ)
        env.update({
            "HVT_TS_WORKER": "1",
            "HVT_TS_RANK": str(r),
            "HVT_TS_SIZE": str(np_),
            "HVT_TS_PORT": str(engine_port),
            "HVT_TS_KV": kv_addr,
            "HVT_TS_SPEC": json.dumps(spec),
            "HVT_TELEMETRY_ROLE": role,
            "HVT_TOPO_HOST": f"h{host_i}",
            "HVT_HOSTNAME": "127.0.0.1",
            "HVT_CTRL_TOPOLOGY": "star",
            "HVT_CONNECT_TIMEOUT": "240",
            "HVT_LOG_LEVEL": "error",
            "PYTHONUNBUFFERED": "1",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            cwd=REPO,
            stdout=subprocess.PIPE if r == 0 else subprocess.DEVNULL,
            stderr=subprocess.PIPE if r == 0 else subprocess.DEVNULL,
            text=True))
    return procs


def check_statusz_doc(doc, np_):
    """Schema assertions shared by the artifact capture, the hvt_top
    round-trip, and tests."""
    errs = []
    if not isinstance(doc, dict):
        return ["statusz: not a JSON object"]
    if doc.get("schema") != "hvt-statusz-r1":
        errs.append(f"statusz schema {doc.get('schema')!r}")
    for key in ("ranks", "hosts", "alerts", "rates", "mode",
                "ranks_covered", "ranks_expected", "stragglers",
                "serving"):
        if key not in doc:
            errs.append(f"statusz missing {key}")
    if np_ is not None and doc.get("ranks_covered") != np_:
        errs.append(f"statusz covers {doc.get('ranks_covered')} of "
                    f"{np_} ranks")
    return errs


def _consistency(doc):
    """Leader-mode merge equivalence: the per-host merged counter must
    equal the sum of the same frame's per-rank compact records, and the
    frame rank sets must tile the covered set."""
    merged = 0.0
    compact_sum = 0.0
    covered = set()
    for h in (doc.get("hosts") or {}).values():
        fr = h.get("metrics") or {}
        fam = (fr.get("metrics") or {}).get("hvt_ctrl_tx_bytes_total") \
            or {}
        merged += sum(s.get("value", 0) for s in fam.get("samples", ()))
        covered.update(h.get("ranks") or ())
    for r, rec in (doc.get("ranks") or {}).items():
        compact_sum += (rec.get("bytes") or {}).get("ctrl_tx", 0)
    return {
        "merged_ctrl_tx": merged,
        "compact_sum_ctrl_tx": compact_sum,
        "identical": abs(merged - compact_sum) < 0.5,
        "frame_ranks": len(covered),
    }


def run_config(np_, hosts, mode, spec, port, timeout=600,
               extra_env=None, hvt_top_probe=False):
    server, kv_addr = start_driver(np_, hosts)
    procs = []
    result = {"np": np_, "hosts": hosts, "mode": mode,
              "interval_sec": spec["interval_sec"]}
    try:
        procs = spawn_workers(np_, hosts, mode, spec, port, kv_addr,
                              extra_env=extra_env)
        deadline = time.monotonic() + timeout

        def check_rank0_alive():
            if procs and procs[0].poll() is not None:
                out, err = procs[0].communicate(timeout=5)
                raise RuntimeError(
                    f"rank 0 exited rc={procs[0].returncode} "
                    f"mid-run:\n{out}\n{err}")

        # readiness: every rank visible in the rollup
        while True:
            check_rank0_alive()
            doc = server.statusz_snapshot()
            if doc.get("ranks_covered", 0) >= np_:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"gang never became visible: "
                    f"{doc.get('ranks_covered')}/{np_} ranks")
            time.sleep(0.3)

        # measurement window: ingest bytes over N push windows
        windows = spec.get("measure_windows", 3)
        w_sec = windows * spec["interval_sec"]
        i0 = server.store.ingest_stats()
        t0 = time.monotonic()
        lat_ms = []
        alerts_seen = []
        while time.monotonic() - t0 < w_sec:
            g0 = time.monotonic()
            doc = _get_json(kv_addr, "/statusz")
            lat_ms.append((time.monotonic() - g0) * 1e3)
            alerts_seen.extend(a.get("rule") for a in
                               doc.get("alerts") or ())
            time.sleep(max(0.2, spec["interval_sec"] / 3))
        elapsed = time.monotonic() - t0
        i1 = server.store.ingest_stats()
        bytes_total = sum(
            i1["put_bytes"].get(s, 0) - i0["put_bytes"].get(s, 0)
            for s in MEASURED_SCOPES)
        puts_total = sum(
            i1["put_count"].get(s, 0) - i0["put_count"].get(s, 0)
            for s in MEASURED_SCOPES)
        per_window = bytes_total * spec["interval_sec"] / elapsed
        result.update({
            "measure_sec": round(elapsed, 2),
            "ingest_bytes": bytes_total,
            "ingest_puts": puts_total,
            "bytes_per_window": round(per_window, 1),
            "puts_per_window": round(
                puts_total * spec["interval_sec"] / elapsed, 1),
            "statusz_get_ms_p50": round(statistics.median(lat_ms), 2),
            "alerts_during_run": sorted(set(alerts_seen)),
        })

        # wait for the gang's deterministic final frames
        while server.store.get("ctl", "done") is None:
            check_rank0_alive()
            if time.monotonic() > deadline:
                raise RuntimeError("gang never reached the done key")
            time.sleep(0.2)
        final = server.statusz_snapshot()
        errs = check_statusz_doc(final, np_)
        result["statusz_errors"] = errs
        result["ranks_covered"] = final.get("ranks_covered")
        result["statusz_mode"] = final.get("mode")
        if mode == "leader":
            result["consistency"] = _consistency(final)

        if hvt_top_probe:
            # the CI round-trip: the tool, as shipped, against the live
            # server (full package import — jax — hence driver-side and
            # once, not per worker)
            out = subprocess.run(
                [sys.executable, "-m", "horovod_tpu.tools.hvt_top",
                 "--addr", kv_addr, "--once", "--json"],
                cwd=REPO, capture_output=True, text=True, timeout=300,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            try:
                top_doc = json.loads(out.stdout)
                top_errs = check_statusz_doc(top_doc, np_)
            except ValueError:
                top_errs = [f"hvt_top emitted no JSON: "
                            f"{out.stdout[:200]!r} / "
                            f"{out.stderr[-300:]!r}"]
            result["hvt_top_errors"] = top_errs
        server.store.put("ctl", "exit", b"1")
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
    return result


def capture(out_path, smoke=False):
    from benchmarks.ctrl_plane_scaling import _next_port

    if smoke:
        np_, hosts = 8, 2
        spec = {"interval_sec": 0.8, "work_sec": 14.0, "tensors": 2,
                "numel": 32, "step_sleep": 0.3, "measure_windows": 3,
                "cycle_ms": 2}
    else:
        np_, hosts = 64, 8
        spec = {"interval_sec": 1.25, "work_sec": 30.0, "tensors": 2,
                "numel": 32, "step_sleep": 0.5, "measure_windows": 4,
                "cycle_ms": 2}
    # loaded-1-core-box allowance: a push delayed by CPU contention
    # must read as late, not dead (the committed false-positive pin is
    # "no alerts on a clean gang", with the stale threshold at 6
    # intervals instead of the production default 3)
    extra_env = {"HVT_HEALTH_STALE_INTERVALS": "6",
                 "HVT_KV_TTL_SEC": "300"}
    os.environ.update(extra_env)
    record = {"schema": SCHEMA, "mode": "smoke" if smoke else "full",
              "lib": os.path.relpath(LIB, REPO),
              "spec": spec, "configs": [], "claims": {}}
    results = {}
    for mode in ("direct", "leader"):
        t0 = time.monotonic()
        res = run_config(np_, hosts, mode, spec, _next_port(),
                         extra_env=extra_env,
                         hvt_top_probe=(mode == "leader"))
        res["total_sec"] = round(time.monotonic() - t0, 1)
        results[mode] = res
        record["configs"].append(res)
        print(json.dumps({k: res.get(k) for k in
                          ("mode", "bytes_per_window",
                           "puts_per_window", "statusz_get_ms_p50",
                           "ranks_covered", "total_sec")}), flush=True)

    d, l = results["direct"], results["leader"]
    cons = l.get("consistency") or {}
    record["claims"] = {
        "ranks": np_, "hosts": hosts,
        "scrape_bytes_per_window_direct": d["bytes_per_window"],
        "scrape_bytes_per_window_leader": l["bytes_per_window"],
        "scrape_puts_per_window_direct": d["puts_per_window"],
        "scrape_puts_per_window_leader": l["puts_per_window"],
        "reduction_x": round(
            d["bytes_per_window"] / max(l["bytes_per_window"], 1), 2),
        "statusz_get_ms_p50": l["statusz_get_ms_p50"],
        "ranks_covered_direct": d["ranks_covered"],
        "ranks_covered_leader": l["ranks_covered"],
        "counter_sum_identical": bool(cons.get("identical")),
        "alerts_clean": not (d["alerts_during_run"]
                             or l["alerts_during_run"]),
        "hvt_top_roundtrip": not l.get("hvt_top_errors", ["missing"]),
    }
    for res in results.values():
        if res.get("statusz_errors"):
            record["claims"]["alerts_clean"] = False
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        print(f"wrote {out_path}")
    print("claims: " + json.dumps(record["claims"]))
    return record


def check(path):
    """Artifact schema + claims validation (ci.sh --obs). The full
    artifact gates the headline ≥4x scrape-byte reduction; the smoke
    pair (2 hosts — less to aggregate) gates a looser 1.5x so the CI
    smoke still proves direction without a 64-proc spawn."""
    with open(path) as f:
        rec = json.load(f)
    errs = []
    if rec.get("schema") != SCHEMA:
        errs.append(f"schema != {SCHEMA}")
    cfgs = rec.get("configs", [])
    modes = {c.get("mode") for c in cfgs}
    if modes != {"direct", "leader"}:
        errs.append(f"configs must cover direct+leader, got {modes}")
    for c in cfgs:
        for key in ("np", "hosts", "bytes_per_window", "puts_per_window",
                    "statusz_get_ms_p50", "ranks_covered"):
            if key not in c:
                errs.append(f"config {c.get('mode')} missing {key}")
        if c.get("statusz_errors"):
            errs.append(f"{c.get('mode')}: statusz errors "
                        f"{c['statusz_errors']}")
    cl = rec.get("claims") or {}
    if not cl:
        errs.append("no claims block")
    else:
        floor = 4.0 if rec.get("mode") == "full" else 1.5
        if (cl.get("reduction_x") or 0) < floor:
            errs.append(f"reduction_x {cl.get('reduction_x')} < {floor}")
        for k in ("ranks_covered_direct", "ranks_covered_leader"):
            if cl.get(k) != cl.get("ranks"):
                errs.append(f"{k}={cl.get(k)} != ranks {cl.get('ranks')}")
        for k in ("counter_sum_identical", "alerts_clean",
                  "hvt_top_roundtrip"):
            if cl.get(k) is not True:
                errs.append(f"claim {k} is {cl.get(k)!r}, want true")
    for e in errs:
        print(f"telemetry_scaling --check: {e}", file=sys.stderr)
    if errs:
        return 1
    print(f"telemetry_scaling --check: OK ({len(cfgs)} configs, "
          f"claims: {json.dumps(cl)})")
    return 0


def main():
    if os.environ.get("HVT_TS_WORKER"):
        _worker()
        return 0
    _stub_package()
    args = sys.argv[1:]

    def argval(flag, dflt):
        if flag not in args:
            return dflt
        i = args.index(flag) + 1
        if i >= len(args):
            sys.exit(f"telemetry_scaling: {flag} requires a value")
        return args[i]

    if "--check" in args:
        return check(argval("--check", ""))
    out = argval("--out", "" if "--smoke" in args
                 else os.path.join(REPO, "benchmarks",
                                   "r13_telemetry_scaling.json"))
    capture(out, smoke="--smoke" in args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
