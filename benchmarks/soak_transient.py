#!/usr/bin/env python
"""Transient-fault soak: seeded randomized chaos over a 4-proc gang.

The acceptance story of the self-healing links (csrc/transport.h) at
campaign scale: N rounds of the SAME deterministic workload, round 0
with injection off (the reference CRC), every later round with a
seeded random TRANSIENT fault spec (flaky_conn / delay_ms / partition /
reset_storm) injected mid-run. Every round must produce the
bit-identical result CRC on every rank with ZERO aborts, and the soak
as a whole must have actually exercised ≥1 reconnect — otherwise the
schedule was a no-op and the run fails rather than vacuously passing.

Usage:
  python benchmarks/soak_transient.py [--rounds 4] [--seed 5]
      [--np 4] [--ops 16] [--numel 65536] [--out artifact.json]

Wired as `./ci.sh --soak` (non-tier-1, like --chaos). Exit 0 = every
invariant held.
"""

import argparse
import json
import os
import random
import socket
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = """
import os, sys, zlib
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvt
from horovod_tpu.engine import native
hvt.init()
r, n = hvt.rank(), hvt.size()
crc = 0
for i in range({ops}):
    # deterministic mixed-size payloads; every rank contributes
    numel = {numel} if i % 3 else {numel} * 4
    x = (np.arange(numel, dtype=np.float32) * (r + 1) + i).astype(np.float32)
    res = np.asarray(hvt.allreduce(x, op=hvt.Sum, name=f"soak.{{i}}"))
    crc = zlib.crc32(res.tobytes(), crc)
st = native.engine_stats()
broken, info = native.engine_broken()
out = {{
    "rank": r,
    "crc": crc,
    "aborts": sum(st["aborts"].values()),
    "broken": bool(broken),
    "reconnects": sum(st["link_reconnects"].values()),
    "replay_bytes": st["replay_bytes"],
    "frames_replayed": st["frames_replayed"],
}}
print("SOAK-RESULT " + __import__("json").dumps(out), flush=True)
hvt.shutdown()
"""


def _next_port():
    p = 24000 + (os.getpid() * 577) % 8000
    while True:
        p += 1
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", p))
                return p
            except OSError:
                continue


def _fault_schedule(rng, np_, rounds):
    """One transient spec per fault round, drawn from the seeded RNG.
    Every spec here must be SURVIVABLE: the gang heals, zero aborts."""
    specs = []
    for _ in range(rounds):
        kind = rng.choice(["flaky_conn", "delay_ms", "partition",
                           "reset_storm"])
        if kind == "flaky_conn":
            specs.append("flaky_conn:rank=%d:count=%d:after_ops=%d"
                         % (rng.randrange(np_), rng.randint(1, 2),
                            rng.randint(2, 5)))
        elif kind == "delay_ms":
            specs.append("delay_ms:rank=%d:%d"
                         % (rng.randrange(np_), rng.randint(20, 60)))
        elif kind == "partition":
            specs.append("partition:hosts=hA|hB:ms=%d"
                         % rng.randint(200, 500))
        else:
            specs.append("reset_storm:every_ops=%d:rank=%d"
                         % (rng.randint(3, 5), rng.randrange(np_)))
    return specs


def _run_round(script_path, np_, spec, timeout_sec, logdir, tag):
    port = _next_port()
    procs, logs = [], []
    for rank in range(np_):
        env = dict(os.environ)
        env.update({
            "HVT_MASTER_ADDR": "127.0.0.1",
            "HVT_MASTER_PORT": str(port),
            "HVT_PROCESS_ID": str(rank),
            "HVT_NUM_PROCESSES": str(np_),
            "HVT_SHM_ALLREDUCE": "0",      # the TCP plane is under test
            "HVT_HIERARCHICAL_ALLREDUCE": "0",
            # fake a 2-host split so partition specs have a boundary
            "HVT_TOPO_HOST": "hA" if rank < np_ // 2 else "hB",
            "HVT_OP_TIMEOUT_MS": "30000",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "",
        })
        if spec:
            env["HVT_FAULT_INJECT"] = spec
        else:
            env.pop("HVT_FAULT_INJECT", None)
        log = open(os.path.join(logdir, f"soak_{tag}_r{rank}.log"), "w+")
        procs.append(subprocess.Popen(
            [sys.executable, script_path], env=env, cwd=REPO,
            stdout=log, stderr=subprocess.STDOUT))
        logs.append(log)
    deadline = time.time() + timeout_sec
    codes = []
    for p in procs:
        try:
            codes.append(p.wait(timeout=max(1, deadline - time.time())))
        except subprocess.TimeoutExpired:
            p.kill()
            codes.append("TIMEOUT")
    results = []
    for log in logs:
        log.flush()
        log.seek(0)
        text = log.read()
        log.close()
        res = None
        for ln in text.splitlines():
            if ln.startswith("SOAK-RESULT "):
                res = json.loads(ln[len("SOAK-RESULT "):])
        results.append((res, text))
    return codes, results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rounds", type=int, default=4,
                    help="fault rounds after the baseline (default 4)")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--np", type=int, default=4, dest="nproc")
    ap.add_argument("--ops", type=int, default=16)
    ap.add_argument("--numel", type=int, default=65536)
    ap.add_argument("--timeout", type=int, default=180,
                    help="per-round hard timeout (seconds)")
    ap.add_argument("--out", default=None,
                    help="write the soak artifact JSON here")
    ap.add_argument("--logdir", default="/tmp")
    args = ap.parse_args(argv)

    rng = random.Random(args.seed)
    specs = [None] + _fault_schedule(rng, args.nproc, args.rounds)
    script = os.path.join(args.logdir, f"hvt_soak_{os.getpid()}.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent(_WORKER.format(
            repo=REPO, ops=args.ops, numel=args.numel)))

    failures = []
    rounds_out = []
    ref_crc = None
    total_reconnects = 0
    for i, spec in enumerate(specs):
        tag = "base" if spec is None else f"f{i}"
        codes, results = _run_round(script, args.nproc, spec,
                                    args.timeout, args.logdir, tag)
        row = {"round": i, "spec": spec or "none", "codes": codes}
        crcs, recon, aborts = [], 0, 0
        for rank, (res, text) in enumerate(results):
            if codes[rank] != 0 or res is None:
                failures.append(
                    f"round {i} ({spec or 'baseline'}): rank {rank} "
                    f"rc={codes[rank]}\n{text[-2000:]}")
                continue
            crcs.append(res["crc"])
            recon += res["reconnects"]
            aborts += res["aborts"]
            if res["broken"]:
                failures.append(f"round {i}: rank {rank} engine broken")
        row.update(crcs=crcs, reconnects=recon, aborts=aborts)
        rounds_out.append(row)
        if len(crcs) == args.nproc:
            if len(set(crcs)) != 1:
                failures.append(f"round {i}: ranks disagree on the "
                                f"result CRC: {crcs}")
            elif ref_crc is None:
                ref_crc = crcs[0]
            elif crcs[0] != ref_crc:
                failures.append(
                    f"round {i} ({spec}): CRC {crcs[0]:#x} != "
                    f"injection-off baseline {ref_crc:#x} — the healed "
                    f"run is NOT bit-identical")
        if aborts:
            failures.append(f"round {i} ({spec or 'baseline'}): "
                            f"{aborts} abort(s) — a transient fault "
                            f"escalated")
        if spec is not None:
            total_reconnects += recon
        print(f"[soak] round {i} spec={spec or 'none':<44} "
              f"crc={'%08x' % crcs[0] if crcs else '????'} "
              f"reconnects={recon} aborts={aborts}", flush=True)

    if total_reconnects < 1:
        failures.append("the whole soak recorded ZERO reconnects — the "
                        "fault schedule never bit (seed too tame?)")

    artifact = {
        "schema": "hvt-soak-r1",
        "seed": args.seed,
        "np": args.nproc,
        "ops": args.ops,
        "baseline_crc": ref_crc,
        "rounds": rounds_out,
        "total_reconnects": total_reconnects,
        "ok": not failures,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"[soak] artifact -> {args.out}")
    if failures:
        print("\n[soak] FAILED:", file=sys.stderr)
        for fl in failures:
            print(" - " + fl, file=sys.stderr)
        return 1
    print(f"[soak] OK: {len(specs) - 1} fault rounds bit-identical to "
          f"baseline, {total_reconnects} reconnects, zero aborts")
    return 0


if __name__ == "__main__":
    sys.exit(main())
