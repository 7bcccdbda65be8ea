"""Gang tests for the control-plane scale-out (PR 8): hierarchical
tree negotiation (``HVT_CTRL_TOPOLOGY=tree``), the steady-state
cache-hit bypass (bitmask announce votes + positions-form responses),
eviction-broadcast position sync under tree mode, coordinated-abort
fan-out when a LEADER dies, and the idle-gang traffic reduction at
rank 0.

Every test launches REAL multi-process engine gangs over loopback, but
through the featherweight ctypes harness of
``benchmarks/ctrl_plane_scaling.py`` (no jax/numpy import per worker),
so a 16-rank gang costs seconds, not minutes. ``HVT_TOPO_HOST`` fakes
the multi-host layout the leader election keys on.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build",
                   "libhvt_core.so")

sys.path.insert(0, REPO)
from benchmarks import ctrl_plane_scaling as cps  # noqa: E402

pytestmark = pytest.mark.skipif(
    not os.path.exists(LIB),
    reason="C++ engine not built (make -C horovod_tpu/csrc)")


def run_gang(body, np=4, hosts=2, topology="tree", timeout=120,
             extra_env=None, expect_rc=0):
    """Spawn np featherweight workers running `body` with ``eng``
    (an initialized MiniEngine), ``r``, ``n`` in scope. Workers write
    ``OUT`` (a JSON-able dict) to a per-rank file; returns
    {rank: out_dict}. Ranks pack contiguously onto `hosts` fake
    hosts."""
    port = cps._next_port()
    import tempfile
    outdir = tempfile.mkdtemp(prefix=f"hvt_cptest_{port}_")
    script = textwrap.dedent(f"""
        import json, os, sys, time, zlib
        sys.path.insert(0, {REPO!r})
        from benchmarks.ctrl_plane_scaling import MiniEngine
        r = int(os.environ["HVT_CP_RANK"])
        n = {np}
        eng = MiniEngine()
        eng.init(r, n, port={port}, cycle_ms=1)
        OUT = {{}}
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        with open(os.path.join({outdir!r}, f"rank{{r}}.json"), "w") as f:
            json.dump(OUT, f)
        eng.shutdown()
        print(f"WORKER-{{r}}-DONE", flush=True)
    """)
    path = os.path.join(outdir, "worker.py")
    with open(path, "w") as f:
        f.write(script)
    per_host = max(1, np // hosts)
    procs = []
    try:
        for r in range(np):
            env = dict(os.environ)
            env.update({
                "HVT_CP_RANK": str(r),
                "HVT_CTRL_TOPOLOGY": topology,
                "HVT_HOSTNAME": "127.0.0.1",
                "HVT_TOPO_HOST": f"h{min(r // per_host, hosts - 1)}",
                "HVT_LOG_LEVEL": "error",
                "PYTHONUNBUFFERED": "1",
            })
            env.update(extra_env or {})
            procs.append(subprocess.Popen(
                [sys.executable, path], env=env, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = {}
        deadline = time.monotonic() + timeout
        for r, p in enumerate(procs):
            try:
                out, _ = p.communicate(
                    timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate(timeout=30)
                raise AssertionError(
                    f"rank {r} timed out after {timeout}s:\n{out}")
            outs[r] = out
            if expect_rc is not None:
                assert p.returncode == expect_rc, \
                    f"rank {r} rc={p.returncode} (want {expect_rc}):" \
                    f"\n{out}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results = {}
    for r in range(np):
        rp = os.path.join(outdir, f"rank{r}.json")
        if os.path.exists(rp):
            with open(rp) as f:
                results[r] = json.load(f)
    return results, outs


# The body every bit-identity gang runs: a spread of ops, dtypes, and
# reduce kinds, digested per rank with crc32 so star and tree runs can
# be compared byte-for-byte.
_IDENTITY_BODY = """
import struct
digests = []
def digest(tag, vals, fmt):
    digests.append((tag, zlib.crc32(struct.pack(f"<{len(vals)}{fmt}",
                                                *vals))))
for dtype, fmt in (("float32", "f"), ("float64", "d"), ("int32", "i"),
                   ("int64", "q"), ("uint8", "B")):
    base = [(i % 23 + r + 1) % (120 if fmt == "B" else 10**6)
            for i in range(257)]
    out = eng.collective(f"ar.{dtype}", base, dtype=dtype)
    digest(f"ar.{dtype}", out, fmt)
for red in ("min", "max", "prod"):
    vals = [float((i * (r + 3)) % 7 + 1) for i in range(65)]
    out = eng.collective(f"ar.{red}", vals, reduce=red)
    digest(f"ar.{red}", out, "f")
out = eng.collective("bc", [float(r * 100 + i) for i in range(33)],
                     op="broadcast", root=1)
digest("bc", out, "f")
out = eng.collective("ag", [float(r + i) for i in range(9)],
                     op="allgather")
digest("ag", out, "f")
# repeated-name traffic: steady-state cycles ride the bypass
for step in range(6):
    out = eng.collective("hot", [float(r + 1)] * 129)
    digest(f"hot.{step}", out, "f")
# subset collectives: two disjoint lanes reusing one name
half = [0, 1] if r < 2 else [2, 3]
out = eng.collective("lane", [float(r + 1)] * 17, members=half)
digest("lane", out, "f")
st = eng.stats()
OUT = {"digests": digests, "cache_hits": st["cache_hits"],
       "bypass_cycles": st["ctrl_bypass_cycles"],
       "ctrl_peers": st["ctrl_peers"]}
"""


def test_star_tree_bit_identity():
    """The tree control plane must produce bit-identical collective
    results to the star baseline — same ops, same dtypes, same reduce
    kinds, including cache-hit steady-state traffic and subset lanes."""
    star, _ = run_gang(_IDENTITY_BODY, np=4, hosts=2, topology="star")
    tree, _ = run_gang(_IDENTITY_BODY, np=4, hosts=2, topology="tree")
    assert set(star) == set(tree) == {0, 1, 2, 3}
    for r in range(4):
        assert star[r]["digests"] == tree[r]["digests"], \
            f"rank {r} results diverge between star and tree"
    # the steady-state phase really did ride the cache + bypass
    assert tree[0]["cache_hits"] > 0
    assert tree[0]["bypass_cycles"] > 0
    # fan-in: star root serves world-1 peers, tree root one per host
    assert star[0]["ctrl_peers"] == 3
    assert tree[0]["ctrl_peers"] == 2


def test_bitmask_vote_mixed_cycles_and_lanes():
    """Cache-bitmask votes must stay correct when hit and miss traffic
    land in the same cycle, and per-lane (process-set) positions must
    not cross-talk — each lane's steady state bypasses independently."""
    body = """
    half = [0, 1] if r < 2 else [2, 3]
    expect_half = 3.0 if r < 2 else 7.0
    # lane-specific names: the response cache is keyed by NAME, so two
    # lanes sharing one steady-state name would thrash each other's
    # entry (documented; fine for correctness, fatal for hit rate)
    lane_nm = f"lane.h{half[0]}"
    eng.collective(lane_nm, [float(r + 1)] * 33, members=half)
    eng.collective("glob.a", [float(r + 1)] * 33)
    errs = []
    for step in range(8):
        hs = []
        # pure-hit submissions (bitmask-vote eligible)...
        hs.append(eng.submit(lane_nm, [float(r + 1)] * 33,
                             members=half))
        hs.append(eng.submit("glob.a", [float(r + 1)] * 33))
        # ...plus, on some steps, a fresh miss in the same cycle
        if step % 3 == 0:
            hs.append(eng.submit(f"fresh.{step}", [2.0] * 9))
        outs = [eng.wait(h) for h in hs]
        if abs(outs[0][0] - expect_half) > 1e-6:
            errs.append(("lane", step, outs[0][0]))
        if abs(outs[1][0] - 10.0) > 1e-6:
            errs.append(("glob", step, outs[1][0]))
        if len(outs) > 2 and abs(outs[2][0] - 8.0) > 1e-6:
            errs.append(("fresh", step, outs[2][0]))
    # cross-lane SAME-name correctness (cache-thrash case): both lanes
    # reuse one name; values must still come out right every time
    for step in range(3):
        out = eng.collective("shared.nm", [float(r + 1)] * 5,
                             members=half)
        if abs(out[0] - expect_half) > 1e-6:
            errs.append(("shared", step, out[0]))
    st = eng.stats()
    OUT = {"errs": errs, "cache_hits": st["cache_hits"],
           "bypass_cycles": st["ctrl_bypass_cycles"]}
    """
    results, _ = run_gang(body, np=4, hosts=2, topology="tree")
    for r, out in results.items():
        assert out["errs"] == [], f"rank {r}: {out['errs']}"
    assert results[0]["cache_hits"] > 0
    # pure-hit cycles rode the positions-form bypass on every rank
    for r in range(4):
        assert results[r]["bypass_cycles"] > 0, results[r]


def test_eviction_broadcast_position_sync_tree():
    """Re-submitting a cached name with changed params must evict the
    position on EVERY rank (broadcast through the tree) and renegotiate
    cleanly — positions drifting across ranks would corrupt later
    cache-hit traffic."""
    body = """
    errs = []
    for round_ in range(3):
        # cache under shape A, hit it, then change shape -> kInvalid
        for step in range(3):
            out = eng.collective("ev", [float(r + 1)] * 40)
            if abs(out[0] - 10.0) > 1e-6:
                errs.append(("A", round_, step, out[0]))
        for step in range(2):
            out = eng.collective("ev", [float(r + 2)] * 72)
            if abs(out[0] - 14.0) > 1e-6:
                errs.append(("B", round_, step, out[0]))
        # a second cached name keeps its (synced) position throughout
        out = eng.collective("stable", [1.0] * 16)
        if abs(out[0] - 4.0) > 1e-6:
            errs.append(("stable", round_, out[0]))
    OUT = {"errs": errs}
    """
    results, _ = run_gang(body, np=4, hosts=2, topology="tree")
    for r, out in results.items():
        assert out["errs"] == [], f"rank {r}: {out['errs']}"


def test_leader_death_aborts_gang_within_deadline():
    """SIGKILL the LEADER of host h1 (rank 2) mid-run: every survivor
    — its member (behind the dead leader), the other host, and the
    root — must error out within ~one op deadline, not hang."""
    body = """
    t0 = time.monotonic()
    aborted = None
    try:
        for step in range(200):
            eng.collective(f"work.{step % 4}", [float(r)] * 257)
    except RuntimeError as e:
        aborted = time.monotonic() - t0
        msg = str(e)
    OUT = {"aborted_sec": aborted,
           "msg": msg[:200] if aborted else ""}
    """
    timeout_ms = 4000
    results, outs = run_gang(
        body, np=4, hosts=2, topology="tree", timeout=90,
        extra_env={
            "HVT_FAULT_INJECT": "kill:rank=2:after_ops=20",
            "HVT_OP_TIMEOUT_MS": str(timeout_ms),
            "HVT_HEARTBEAT_MS": str(timeout_ms),
        },
        expect_rc=None)  # rank 2 dies by SIGKILL; checked below
    # rank 2 was killed before writing its OUT file
    assert 2 not in results, "the fault never fired"
    for r in (0, 1, 3):
        assert r in results, f"survivor {r} wrote no result:\\n{outs[r]}"
        took = results[r]["aborted_sec"]
        assert took is not None, f"survivor {r} never aborted"
        # containment bound: ~one deadline + fan-out slack (the
        # existing chaos suite uses the same 2x bound)
        assert took < 2.5 * timeout_ms / 1e3, \
            f"survivor {r} took {took:.1f}s to abort: {results[r]}"


def test_idle_16rank_rank0_traffic_drops_under_tree():
    """The idle-gang keepalive exchange routes through leaders in tree
    mode: a parked 16-rank gang on 4 simulated hosts must cost rank 0
    a fraction of the star's control bytes (15 direct peers -> 4)."""
    spec = {"tensors": 2, "numel": 16,
            "phases": [{"name": "idle", "sleep": 2.0}]}
    star = cps.run_config(16, 4, "star", spec, cps._next_port(),
                          timeout=180)
    tree = cps.run_config(16, 4, "tree", spec, cps._next_port(),
                          timeout=180)
    assert star["ctrl_peers"] == 15
    assert tree["ctrl_peers"] == 4
    # per-CYCLE bytes: wall-clock rates skew with box load, the bytes a
    # keepalive cycle moves do not. 15 -> 4 peers cuts ~2.5x (aggregate
    # keepalives carry a per-rank roster, so not the full 3.75x).
    sph, tph = star["phases"]["idle"], tree["phases"]["idle"]
    sb = (sph["ctrl_tx_bytes"] + sph["ctrl_rx_bytes"]) \
        / max(sph["cycles"], 1)
    tb = (tph["ctrl_tx_bytes"] + tph["ctrl_rx_bytes"]) \
        / max(tph["cycles"], 1)
    assert sb > tb * 2, (sb, tb)
