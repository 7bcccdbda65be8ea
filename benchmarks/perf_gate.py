#!/usr/bin/env python
"""Perf-regression gate capture (``ci.sh --perfgate``).

Produces ONE self-describing perf report by running

1. the existing loopback microbench (``engine_scaling.py --sweep``
   internals: hot-path allreduce p50 per payload size on the TCP ring,
   interleaved rounds, best-round p50 as the least-interference
   estimate), and
2. a short **flight-recorded 2-proc gang** (``--timeline`` +
   ``HVT_TIMELINE_MARK_CYCLES=1``, shm off so the TCP duplex pump's
   WIRE spans are exercised), analyzed by
   ``horovod_tpu.tools.hvt_analyze`` into the queue / negotiate / wire /
   reduce phase breakdown.

The report's ``metrics`` block carries the CURATED gate set — sweep
p50s plus the gang's queue/wire/exec/e2e p50s. Noisy low-sample series
(cold-negotiation p50, stragglers, p99s) stay in the report for humans
but never gate: the contract is *fail only on >2x p50 regressions*
(``hvt_analyze --diff``, band overridable via
``HVT_PERFGATE_MAX_RATIO``), with bands generous enough for a shared CI
box.

Usage:
    python benchmarks/perf_gate.py --out /tmp/perf.json   # capture
    python benchmarks/perf_gate.py --rebaseline           # refresh
        benchmarks/perf_baseline.json (commit the result)
    python -m horovod_tpu.tools.hvt_analyze --diff \\
        benchmarks/perf_baseline.json /tmp/perf.json      # the gate
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)  # benchmarks/ is not a package

from horovod_tpu.tools.hvt_analyze import _pctl  # noqa: E402

SCHEMA = "hvt-perfgate-r1"

# fp32 element counts: latency floor, mid, bandwidth-bound
SWEEP_SIZES = {"4KB": 1 << 10, "1MB": 1 << 18, "16MB": 1 << 22}

GANG_WORKER = """\
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvt
hvt.init()
x = np.arange(1 << 14, dtype=np.float32)  # 64 KB
for i in range({iters}):
    hvt.allreduce(x, name="gate.hot")
# a small async window so the overlap metric sees in-flight work
hs = [hvt.allreduce_async(x, name=f"gate.async.{{j}}") for j in range(4)]
for h in hs:
    hvt.synchronize(h)
hvt.shutdown()
"""

# gang phase p50s that gate (negotiate/stragglers are low-sample noise
# on a quick run and stay report-only)
GANG_GATE_PHASES = ("queue", "wire", "exec", "e2e")


def _free_port():
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_sweep(np_, iters, rounds, sizes):
    """Best-round p50 (ms) per size via the engine_scaling harness; the
    worker subprocesses measure the hot cached-name path on the TCP
    ring (HVT_SHM_ALLREDUCE=0, set inside run_sweep_job)."""
    import engine_scaling

    pooled = {label: [] for label in sizes}
    round_p50 = {label: [] for label in sizes}
    for rnd in range(rounds):
        res = engine_scaling.run_sweep_job(np_, {}, sizes, iters, REPO)
        for label, samples in res["samples_s"].items():
            pooled[label].extend(samples)
            round_p50[label].append(
                _pctl(sorted(samples), 0.50) * 1e3)
        print(f"perf-gate: sweep round {rnd + 1}/{rounds} done",
              flush=True)
    out = {}
    for label in sizes:
        s = sorted(pooled[label])
        out[label] = {
            "p50_ms": round(_pctl(s, 0.50) * 1e3, 3),
            "p99_ms": round(_pctl(s, 0.99) * 1e3, 3),
            "round_p50_ms": [round(v, 3) for v in round_p50[label]],
            "best_p50_ms": round(min(round_p50[label]), 3),
        }
    return out


def run_recorded_gang(np_, iters, timeout_sec=240):
    """Launch a flight-recorded gang and analyze the merged timeline."""
    from horovod_tpu.tools import hvt_analyze

    with tempfile.TemporaryDirectory(prefix="hvt_perfgate_") as td:
        worker = os.path.join(td, "worker.py")
        with open(worker, "w") as f:
            f.write(GANG_WORKER.format(repo=REPO, iters=iters))
        merged = os.path.join(td, "timeline.json")
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",
            # the TCP duplex pump is what the WIRE spans cover; shm
            # would hide the wire phase on a single-host gang
            "HVT_SHM_ALLREDUCE": "0",
            "HVT_TIMELINE_MARK_CYCLES": "1",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch",
             "-np", str(np_), "--master-port", str(_free_port()),
             "--timeline", merged, sys.executable, worker],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout_sec)
        if proc.returncode != 0 or not os.path.exists(merged):
            raise RuntimeError(
                f"perf-gate gang failed (rc={proc.returncode}):\n"
                f"{proc.stdout}\n{proc.stderr}")
        return hvt_analyze.analyze_paths([merged])


def capture(np_=2, sweep_iters=10, sweep_rounds=3, gang_runs=3,
            gang_iters=40, quick=False):
    """Best-of-N everywhere: each measurement is min over repeated
    runs, because on a shared box the quietest run is the
    least-interference estimate (a co-tenant can only make you slower).
    The gate then compares best-of vs best-of, which is what keeps a
    2x band honest on noisy CI hardware."""
    if quick:
        sweep_iters, sweep_rounds, gang_runs, gang_iters = 5, 1, 1, 15
    sweep = run_sweep(np_, sweep_iters, sweep_rounds, SWEEP_SIZES)
    gangs = []
    for i in range(gang_runs):
        gangs.append(run_recorded_gang(np_, gang_iters))
        print(f"perf-gate: gang run {i + 1}/{gang_runs} done",
              flush=True)
    gang = gangs[0]  # full report from the first run; p50s gate best-of
    metrics = {}
    for label, row in sweep.items():
        metrics[f"sweep_{label}_p50_ms"] = row["best_p50_ms"]
    for phase in GANG_GATE_PHASES:
        p50s = [g["phases"][phase]["p50"] for g in gangs
                if phase in g["phases"]]
        if p50s:
            metrics[f"gang_{phase}_us_p50"] = min(p50s)
    return {
        "schema": SCHEMA,
        "np": np_,
        "sweep_iters": sweep_iters,
        "sweep_rounds": sweep_rounds,
        "gang_runs": gang_runs,
        "gang_iters": gang_iters,
        "transport": "tcp ring (HVT_SHM_ALLREDUCE=0)",
        "sweep": sweep,
        "gang": gang,
        "metrics": metrics,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="capture the perf-gate report (microbench sweep + "
                    "flight-recorded gang breakdown)")
    ap.add_argument("--out", default="/tmp/hvt_perf_gate.json",
                    help="report path (default /tmp/hvt_perf_gate.json)")
    ap.add_argument("--rebaseline", action="store_true",
                    help="write benchmarks/perf_baseline.json instead "
                         "(commit the result)")
    ap.add_argument("--np", type=int, default=2)
    ap.add_argument("--quick", action="store_true",
                    help="fewer iterations (smoke runs, not baselines)")
    args = ap.parse_args(argv)
    rep = capture(np_=args.np, quick=args.quick)
    out = (os.path.join(HERE, "perf_baseline.json")
           if args.rebaseline else args.out)
    with open(out, "w") as f:
        json.dump(rep, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"perf-gate: report written to {out}")
    for k, v in sorted(rep["metrics"].items()):
        print(f"  {k} = {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
