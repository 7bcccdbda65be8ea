#!/usr/bin/env python
"""Simulated kill-a-host elastic recovery harness
(``python benchmarks/elastic_recovery.py``).

Proves checkpointless recovery (``horovod_tpu/elastic/state.py
ReplicatedState`` + the leader-routed KV relay) at 128 simulated ranks
on 16 fake hosts: a REAL :class:`ElasticDriver` + ``RendezvousServer``
drive featherweight MiniEngine workers (bare ctypes over
``libhvt_core.so`` — no jax/numpy per worker, same harness family as
``ctrl_plane_scaling.py`` / ``telemetry_scaling.py``), one host is
SIGKILLed mid-training, and the gang recovers through the real elastic
code paths (``elastic/run.py`` slot sync + failure/READY/recovery
reports, driver blacklist + round fold, ``state.sync()`` peer rebuild).

Two arms, identical workload:

- **peer** — ``ReplicatedState`` commits replicate shards to K peers
  every step; recovery rebuilds the lost ranks' state from survivors
  and resumes from the LAST COMMIT. KV reports ride the per-host
  leader relay (``HVT_KV_RELAY=1``).
- **restore** — replication off; every rank checkpoints to disk every
  ``ckpt_every`` steps and recovery restarts the WHOLE gang from the
  last checkpoint (the Horovod-paper elastic story), replaying the
  lost steps. KV reports go direct (the pre-relay wire shape).

Measured claims (committed as ``benchmarks/r14_elastic_recovery.json``):

- **time-to-recovered-throughput** — SIGKILL to the first completed
  post-recovery training step, per arm; the headline gate is peer
  ≥3x faster at the full 128-rank shape (the baseline pays checkpoint
  reload + replay of every step since the last checkpoint; commits
  are per-step, so the peer arm replays at most one).
- **bit-identity** — the final state of EVERY owner lineage (including
  the killed host's, adopted by survivors) must equal an uninterrupted
  run's, byte-for-byte (CRC of the canonical snapshot). The workload
  is world-size-invariant by construction: the per-step gradient is
  identical on every rank and deterministic, so the reference
  trajectory is computable exactly and any rebuild corruption breaks
  the CRC; the per-step avg-allreduce result is asserted against the
  expected value as the engine-correctness probe.
- **driver KV fan-in** — HTTP PUT requests hitting the driver on the
  recovery-path scopes (failure/state/recovery) during the recovery
  window: O(hosts) with the relay (leaders debounce the report burst
  into one /kvbulk each), O(ranks) direct.

Timing columns are wall-clock on a shared box, but the two arms run
back-to-back under the identical workload, so the RATIO is the stable
claim (CHANGES.md PR 7 methodology); byte/request counts are
workload-determined and exact.

Modes:
    --smoke [--out X.json]   16 ranks / 4 hosts pair (ci.sh --elastic)
    --capture [--out ...]    the full 128-rank / 16-host r14 matrix
    --check X.json           artifact schema + claims validation
Worker mode is selected internally via HVT_ER_WORKER.
"""

from __future__ import annotations

import json
import os
import pickle
import signal
import subprocess
import sys
import threading
import time
import types
import zlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA = "hvt-elastic-recovery-r1"
RECOVERY_SCOPES = ("failure", "state", "recovery")


def _stub_package():
    """Register a bare ``horovod_tpu`` package root so submodule
    imports work WITHOUT executing the real package ``__init__`` (which
    imports jax — the weight this harness exists to avoid)."""
    if "horovod_tpu" not in sys.modules:
        pkg = types.ModuleType("horovod_tpu")
        pkg.__path__ = [os.path.join(REPO, "horovod_tpu")]
        sys.modules["horovod_tpu"] = pkg
    if REPO not in sys.path:
        sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# the deterministic workload (shared by workers + the reference model)
# ---------------------------------------------------------------------------

def grad_value(step: int) -> float:
    """The step's gradient component — identical on every rank, so the
    avg-allreduce must return ~v at any world size. State evolution
    uses this DETERMINISTIC value (not the wire result, which can be
    an ULP off through the hierarchical reduction at 128 ranks), which
    is what makes the trajectory world-size-invariant and the
    reference computable exactly; the wire result is asserted against
    it as the per-step engine-correctness probe."""
    return float(1 + step % 7)


def apply_step(params: list, moment: float, owner: int, step: int,
               avg: float):
    """One lineage's state transition. params follow the shared
    trajectory; moment is per-owner, so a rebuilt shard that lost or
    swapped a lineage cannot CRC-match."""
    params[step % len(params)] += avg
    return moment + (owner + 1) * avg


def lineage_crc(params: list, moment: float, step: int) -> int:
    """Canonical snapshot CRC — the bit-identity probe."""
    return zlib.crc32(pickle.dumps((params, moment, step),
                                   protocol=4)) & 0xFFFFFFFF


def simulate_reference(np_: int, numel: int, total_steps: int) -> dict:
    """owner -> final CRC of an uninterrupted run, computed exactly."""
    finals = {}
    for owner in range(np_):
        params = [0.0] * numel
        moment = 0.0
        for step in range(total_steps):
            moment = apply_step(params, moment, owner, step,
                                grad_value(step))
        finals[owner] = lineage_crc(params, moment, total_steps)
    return finals


# ---------------------------------------------------------------------------
# MiniEngine-backed collectives for ReplicatedState
# ---------------------------------------------------------------------------

class MiniCollectives:
    """The four-method collectives backend ``ReplicatedState`` needs,
    over a MiniEngine gang: object allgather = sizes allgather +
    pad-to-max uint8 allgather (the engine's own object-collective
    mechanism, jax/numpy-free). Call names are sequence-tagged so every
    exchange negotiates fresh — shard sizes change across commits and
    rounds."""

    def __init__(self, eng, rank: int, size: int, host: str):
        self.eng = eng
        self._rank = rank
        self._size = size
        self._host = host
        self._seq = {}

    def rebind(self, eng, rank: int, size: int):
        self.eng, self._rank, self._size = eng, rank, size
        # fresh engine = fresh name space. The per-name sequence tags
        # MUST reset with it: re-planned replication groups mix ranks
        # with different historical call counts, and a group whose
        # members tag the same exchange ".35" and ".0" never matches —
        # a silent name-desync wedge (found live at 16 ranks)
        self._seq = {}

    def rank(self) -> int:
        return self._rank

    def size(self) -> int:
        return self._size

    def host(self) -> str:
        return self._host

    def allgather(self, obj, name: str, ranks=None) -> list:
        members = sorted(ranks) if ranks is not None else None
        if members is not None and len(members) == self._size:
            members = None
        seq = self._seq.get(name, 0)
        self._seq[name] = seq + 1
        tag = f"{name}.{seq}"
        payload = pickle.dumps(obj, protocol=4)
        sizes = self.eng.collective(f"{tag}.sz",
                                    [float(len(payload))],
                                    op="allgather", members=members)
        mx = max(1, int(max(sizes)))
        padded = payload + b"\0" * (mx - len(payload))
        data = self.eng.collective(f"{tag}.data", list(padded),
                                   op="allgather", dtype="uint8",
                                   members=members)
        out = []
        for i, sz in enumerate(sizes):
            chunk = bytes(bytearray(data[i * mx:i * mx + int(sz)]))
            out.append(pickle.loads(chunk))
        return out


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def _worker():
    _stub_package()
    import importlib

    from benchmarks.ctrl_plane_scaling import MiniEngine

    # the package exports `run` (the decorator) under the same name as
    # the module; import the MODULE explicitly
    erun = importlib.import_module("horovod_tpu.elastic.run")
    from horovod_tpu.elastic.state import ReplicatedState
    from horovod_tpu.metrics import telemetry as T
    from horovod_tpu.runner.http_client import get_json, put_bytes

    spec = json.loads(os.environ["HVT_ER_SPEC"])
    kv = os.environ["HVT_RENDEZVOUS_ADDR"]
    # identity toward the driver = the fake host, not the dialable one
    erun._identity = (os.environ["HVT_ER_HOST"],
                      os.environ.get("HVT_LOCAL_PROCESS_ID", "0"))
    replicated = os.environ.get("HVT_STATE_REPLICATION", "1") != "0"
    ckpt_dir = spec.get("ckpt_dir")
    numel = spec["numel"]
    total_steps = spec["total_steps"]
    debug = os.environ.get("HVT_ER_DEBUG")

    def trace(msg):
        if debug:
            print(f"[er {os.environ.get('HVT_HOSTNAME')}/"
                  f"{os.environ.get('HVT_LOCAL_PROCESS_ID')}] {msg}",
                  file=sys.stderr, flush=True)

    def progress(body):
        try:
            put_bytes(kv, "/kv/progress/0", json.dumps(body).encode(),
                      timeout=2, retries=0)
        except Exception:
            pass

    def init_engine(eng, rank, size, port):
        import ctypes

        try:
            eng.init(rank, size, port=port,
                     cycle_ms=spec.get("cycle_ms", 2))
        except RuntimeError:
            err = ctypes.create_string_buffer(4096)
            eng.lib.hvt_error_message(err, 4096)
            raise RuntimeError(
                f"hvt_init failed (rank {rank}/{size} port {port}): "
                f"{err.value.decode(errors='replace')}")

    round_ = erun._sync_slot_from_rendezvous(0)
    rank = int(os.environ["HVT_PROCESS_ID"])
    size = int(os.environ["HVT_NUM_PROCESSES"])
    world = get_json(kv, "/world", retries=2)
    eng = MiniEngine()
    init_engine(eng, rank, size, int(world["master_port"]))
    coll = MiniCollectives(eng, rank, size,
                           os.environ.get("HVT_TOPO_HOST", "h?"))
    state = ReplicatedState(collectives=coll, params=[0.0] * numel,
                            moment=0.0, step=0, adopted_lineages={})
    orig_rank = rank
    trace(f"up rank={rank}/{size} round={round_}")

    # the telemetry pusher provides the host-leader endpoint the KV
    # relay routes through (and the /statusz feed); direct-mode arms
    # run it too so both arms carry the same background load
    stop = threading.Event()
    pusher = T.TelemetryPusher(
        kv, rank, lambda: {"rank": rank, "engine": {"running": True}},
        stop, period_sec=spec.get("push_sec", 1.0))
    threading.Thread(target=pusher.run, daemon=True).start()

    def write_ckpt():
        for o, st in [(state.owner if state.owner is not None else rank,
                       {"params": state.params, "moment": state.moment,
                        "step": state.step})] + \
                [(o, dict(st)) for o, st in
                 state.adopted_lineages.items()]:
            path = os.path.join(ckpt_dir, f"owner_{o}.pkl")
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump({"owner": o, "step": state.step, "st": st},
                            f, protocol=4)
            os.replace(tmp, path)

    def restore_from_ckpt():
        """The baseline arm's gang restart-from-checkpoint: every rank
        loads its lineage's last checkpoint (one consistent cut — all
        ranks checkpoint on the same step boundaries) and orphaned
        lineages are adopted round-robin, exactly mirroring the peer
        arm's adoption rule."""
        metas = coll.allgather({"rank": coll.rank(),
                                "owner": state.owner
                                if state.owner is not None else rank},
                               name="er.ckpt_meta")
        claimed = {int(m["owner"]) for m in metas}
        orphans = sorted(set(range(spec["np"])) - claimed)
        ranks_sorted = sorted(int(m["rank"]) for m in metas)
        mine = [o for i, o in enumerate(orphans)
                if ranks_sorted[i % len(ranks_sorted)] == coll.rank()]
        my_owner = state.owner if state.owner is not None else rank
        with open(os.path.join(ckpt_dir,
                               f"owner_{my_owner}.pkl"), "rb") as f:
            rec = pickle.load(f)
        state.params = rec["st"]["params"]
        state.moment = rec["st"]["moment"]
        state.step = rec["st"]["step"]
        state.adopted_lineages = {}
        for o in mine:
            try:
                with open(os.path.join(ckpt_dir,
                                       f"owner_{o}.pkl"), "rb") as f:
                    orec = pickle.load(f)
                state.adopted_lineages[int(o)] = dict(orec["st"])
            except OSError:
                pass
        state._owner = my_owner
        state.save()

    recovered_t = None
    pending_recovered = False
    high_water = 0  # highest step ever completed — "recovered
    # throughput" means training progressed PAST it, so the baseline's
    # checkpoint replay is on the clock, exactly as a user experiences
    while state.step < total_steps:
        try:
            step = state.step
            v = grad_value(step)
            out = eng.collective("step.grad", [v] * numel,
                                 reduce="avg")
            assert abs(out[0] - v) < 1e-3, (out[0], v)
            state.moment = apply_step(state.params, state.moment,
                                      state.owner if state.owner
                                      is not None else rank,
                                      step, v)
            for o, st in state.adopted_lineages.items():
                st["moment"] = apply_step(st["params"], st["moment"],
                                          int(o), step, v)
                st["step"] = step + 1
            state.step = step + 1
            state.commit()
            if pending_recovered and state.step > high_water:
                # throughput is recovered when a post-recovery step
                # completes BEYOND the pre-failure high-water mark —
                # replayed steps are lost work, not recovered work
                recovered_t = time.monotonic()
                pending_recovered = False
            high_water = max(high_water, state.step)
            if not replicated and ckpt_dir and \
                    state.step % spec["ckpt_every"] == 0:
                write_ckpt()
            if orig_rank == 0:
                body = {"step": state.step, "round": round_,
                        "t": time.monotonic()}
                if recovered_t is not None:
                    body["recovered_t"] = recovered_t
                progress(body)
            if spec.get("step_sleep"):
                time.sleep(spec["step_sleep"])
        except RuntimeError as e:
            trace(f"failure at step {state.step}: {e}")
            rec = erun._Recovery("failure")
            if not replicated:
                # the pre-r14 baseline had no per-phase recovery
                # reports; buffering them (only the final "recovered"
                # report PUTs) keeps the restore arm's wire load
                # honest — 120 ranks x 6 phase PUTs would be
                # self-inflicted measurement traffic
                rec.phase = lambda name, seconds, outcome="ok": \
                    rec.phases.append((name, seconds, outcome))
            t0 = time.monotonic()
            erun._report_failure(round_, e)
            rec.phase("report_failure", time.monotonic() - t0)
            t0 = time.monotonic()
            state.restore()
            rec.phase("restore", time.monotonic() - t0)
            t0 = time.monotonic()
            erun._report_state("READY", round_)
            rec.phase("report_ready", time.monotonic() - t0)
            t0 = time.monotonic()
            eng.shutdown()
            rec.phase("shutdown", time.monotonic() - t0)
            t0 = time.monotonic()
            round_ = erun._sync_slot_from_rendezvous(round_)
            rec.phase("rendezvous", time.monotonic() - t0)
            rank = int(os.environ["HVT_PROCESS_ID"])
            size = int(os.environ["HVT_NUM_PROCESSES"])
            world = get_json(kv, "/world", retries=2)
            t0 = time.monotonic()
            init_engine(eng, rank, size, int(world["master_port"]))
            rec.phase("reinit", time.monotonic() - t0)
            coll.rebind(eng, rank, size)
            t0 = time.monotonic()
            if replicated:
                state.sync()
                # fold freshly adopted lineages into the live set the
                # training loop evolves (and future commits replicate)
                for o, snap in state.adopted.items():
                    state.adopted_lineages[int(o)] = {
                        "params": snap["params"],
                        "moment": snap["moment"],
                        "step": snap["step"]}
                rec.phase("rebuild", time.monotonic() - t0,
                          outcome=erun._sync_outcome(state))
            else:
                restore_from_ckpt()
                rec.phase("restore_ckpt", time.monotonic() - t0)
            rec.finish(round_)
            recovered_t = None
            pending_recovered = orig_rank == 0
            trace(f"recovered rank={rank}/{size} at step "
                  f"{state.step}")

    # final barrier, then publish every lineage's CRC
    eng.allreduce("er.final", [1.0])
    finals = {state.owner if state.owner is not None else rank:
              lineage_crc(state.params, state.moment, state.step)}
    for o, st in state.adopted_lineages.items():
        finals[int(o)] = lineage_crc(st["params"], st["moment"],
                                     st["step"])
    for o, crc in finals.items():
        try:
            put_bytes(kv, f"/kv/final/{o}",
                      json.dumps({"crc": crc, "rank": rank}).encode(),
                      timeout=5, retries=2)
        except Exception:
            pass
    eng.allreduce("er.finals_published", [1.0])
    stop.set()
    pusher.close()
    eng.shutdown()


# ---------------------------------------------------------------------------
# driver harness
# ---------------------------------------------------------------------------

class _Gang:
    """Process bookkeeping for one arm's gang: the ElasticDriver's
    create_worker_fn spawns through here so the harness can SIGKILL a
    whole host."""

    def __init__(self, spec, kv_addr, arm):
        self.spec = spec
        self.kv_addr = kv_addr
        self.arm = arm
        self.lock = threading.Lock()
        self.by_host = {}
        self.rank0_out = None
        self._injected = False
        import tempfile

        self.log_dir = tempfile.mkdtemp(prefix="hvt_er_logs_")

    def crash_logs(self, limit=3, tail=1200):
        """Tails of worker logs containing a traceback — the first
        crasher is usually the root cause of a gang-wide wedge."""
        out = []
        try:
            for name in sorted(os.listdir(self.log_dir)):
                path = os.path.join(self.log_dir, name)
                try:
                    with open(path, "rb") as f:
                        data = f.read().decode(errors="replace")
                except OSError:
                    continue
                if "Traceback" in data or "ERROR" in data:
                    out.append(f"--- {name} ---\n{data[-tail:]}")
                if len(out) >= limit:
                    break
        except OSError:
            pass
        return "\n".join(out)

    def spawn(self, slot_info):
        host = slot_info.hostname
        env = dict(os.environ)
        env.update({
            "HVT_ER_WORKER": "1",
            "HVT_ER_SPEC": json.dumps(self.spec),
            "HVT_RENDEZVOUS_ADDR": self.kv_addr,
            # HVT_HOSTNAME is the engine's DIALABLE endpoint host —
            # the fake host name lives in HVT_ER_HOST (driver-facing
            # identity) and HVT_TOPO_HOST (topology identity)
            "HVT_HOSTNAME": "127.0.0.1",
            "HVT_ER_HOST": host,
            "HVT_LOCAL_PROCESS_ID": str(slot_info.local_rank),
            "HVT_TOPO_HOST": host,
            "HVT_TELEMETRY_ROLE": ("leader" if slot_info.local_rank == 0
                                   else "member"),
            "HVT_KV_RELAY": "1" if self.arm == "peer" else "0",
            "HVT_STATE_REPLICATION": "1" if self.arm == "peer" else "0",
            "HVT_REPLICA_GROUP_SIZE": str(self.spec.get("replicas", 2)),
            "HVT_DEBUGZ_INTERVAL_MS": "1000",
            "HVT_RELAY_FLUSH_MS": "700",
            "HVT_KV_TTL_SEC": "600",
            "HVT_CTRL_TOPOLOGY": "star",
            "HVT_CONNECT_TIMEOUT": "240",
            "HVT_LOG_LEVEL": "error",
            # fast, deterministic failure detection: SIGKILL produces
            # RSTs, one short reconnect attempt escalates to the PR 4
            # containment path in well under a second. The op deadline
            # stays WIDE — it only backstops silent wedges, and a
            # 128-rank endpoint exchange on a loaded box can take >15 s
            # (a worker timing out mid-rendezvous kills its listener
            # and wedges everyone else's dials — found live)
            "HVT_LINK_RETRIES": "1",
            "HVT_LINK_RETRY_WINDOW_MS": "800",
            "HVT_OP_TIMEOUT_MS": "60000",
            "PYTHONUNBUFFERED": "1",
        })
        if self.spec.get("fault_inject") and \
                slot_info.rank == self.spec["fault_inject"]["rank"]:
            with self.lock:
                arm_fault = not self._injected
                self._injected = True
            if arm_fault:  # a respawned replacement must not re-die
                env["HVT_FAULT_INJECT"] = \
                    self.spec["fault_inject"]["spec"]
        first = slot_info.rank == 0
        log = None
        if self.log_dir and not first:
            log = open(os.path.join(
                self.log_dir,
                f"{host}_{slot_info.local_rank}.log"), "ab")
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            cwd=REPO,
            stdout=subprocess.PIPE if first else
            (log or subprocess.DEVNULL),
            stderr=subprocess.STDOUT if first else
            (log or subprocess.DEVNULL),
            text=first)
        if log is not None:
            log.close()
        with self.lock:
            self.by_host.setdefault(host, []).append(proc)
            if first:
                self.rank0_out = proc
        return proc.wait()

    def kill_host(self, host):
        with self.lock:
            procs = list(self.by_host.get(host, []))
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGKILL)
                except OSError:
                    pass

    def kill_all(self):
        with self.lock:
            procs = [p for ps in self.by_host.values() for p in ps]
        for p in procs:
            if p.poll() is None:
                p.kill()


def _scope_requests(store, scopes=RECOVERY_SCOPES):
    stats = store.ingest_stats()["put_requests"]
    return {s: stats.get(s, 0) for s in scopes}


def run_arm(arm, spec, timeout=900):
    """One full elastic round-trip for one arm; returns the metrics
    dict. The ElasticDriver, rendezvous server, discovery, registry and
    blacklist logic are the REAL ones — only the workers are
    featherweight."""
    _stub_package()
    from benchmarks.ctrl_plane_scaling import _next_port
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.settings import ElasticSettings
    from horovod_tpu.runner.http_server import RendezvousServer

    np_, hosts = spec["np"], spec["hosts"]
    per_host = np_ // hosts
    target_host = f"h{hosts - 1}"
    rendezvous = RendezvousServer()
    rendezvous.master_port_fn = lambda slots, rnd: _next_port()
    kv_port = rendezvous.start(0)
    kv_addr = f"127.0.0.1:{kv_port}"
    gang = _Gang(spec, kv_addr, arm)
    settings = ElasticSettings(
        min_np=np_ - per_host, max_np=np_, elastic_timeout=180.0,
        reset_limit=6, discovery_interval=0.25)
    driver = ElasticDriver(
        rendezvous,
        FixedHostDiscovery({f"h{i}": per_host for i in range(hosts)}),
        settings, create_worker_fn=gang.spawn)
    result = {"arm": arm, "np": np_, "hosts": hosts}
    deadline = time.monotonic() + timeout
    try:
        driver.start(np_)

        def prog():
            raw = rendezvous.store.get("progress", "0")
            try:
                return json.loads(raw) if raw else {}
            except ValueError:
                return {}

        # phase 1: training reaches the kill step
        t_start = time.monotonic()
        while True:
            p = prog()
            if p.get("step", 0) >= spec["kill_at_step"]:
                break
            if time.monotonic() > deadline or driver.finished():
                raise RuntimeError(
                    f"{arm}: gang never reached kill step "
                    f"(progress={p}, finished={driver.finished()}, "
                    f"err={driver.error})")
            time.sleep(0.05)
        steps_pre = p.get("step", 0)
        result["prekill_steps_per_sec"] = round(
            steps_pre / max(p.get("t", 1) - t_start + 1e-9, 1e-9), 2) \
            if p.get("t") else None
        req0 = _scope_requests(rendezvous.store)
        if spec.get("fault_inject"):
            t_kill = time.monotonic()  # the armed fault fires itself
        else:
            t_kill = time.monotonic()
            gang.kill_host(target_host)
        result["killed_host"] = target_host

        # phase 2: recovery — rank 0 stamps recovered_t (same
        # CLOCK_MONOTONIC domain: all processes share one machine)
        while True:
            p = prog()
            if p.get("recovered_t") and p.get("round", 1) >= 2:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(f"{arm}: gang never recovered "
                                   f"(progress={p})")
            if driver.finished() and driver.error:
                raise RuntimeError(f"{arm}: driver failed mid-"
                                   f"recovery: {driver.error}")
            time.sleep(0.05)
        result["time_to_recovered_sec"] = round(
            p["recovered_t"] - t_kill, 3)
        req1 = _scope_requests(rendezvous.store)
        result["kv_requests_recovery"] = {
            s: req1[s] - req0[s] for s in req1}
        result["kv_requests_recovery_total"] = sum(
            result["kv_requests_recovery"].values())

        # phase 3: run to completion; every surviving worker exits 0
        while not driver.finished():
            if time.monotonic() > deadline:
                raise RuntimeError(f"{arm}: gang never finished")
            time.sleep(0.2)
        if driver.error:
            raise RuntimeError(f"{arm}: driver error: {driver.error}")
        results = driver.get_results()
        bad = {r: rc for r, rc in results.items() if rc != 0}
        if bad:
            raise RuntimeError(f"{arm}: nonzero worker exits {bad}")

        # recovery phase breakdown from rank 0's final /kv/recovery
        # report (the "recovered" report carries per-phase seconds)
        breakdown = {}
        raw = rendezvous.store.get("recovery", "h0/0")
        if raw:
            try:
                body = json.loads(raw)
                breakdown = dict(body.get("phases") or {},
                                 total=body.get("seconds"))
            except (ValueError, TypeError):
                pass
        result["recovery_phases_rank0"] = breakdown

        # bit-identity: every lineage's final CRC vs the reference
        reference = simulate_reference(np_, spec["numel"],
                                       spec["total_steps"])
        finals = {}
        for key in rendezvous.store.keys("final"):
            try:
                finals[int(key)] = json.loads(
                    rendezvous.store.get("final", key))["crc"]
            except (ValueError, TypeError, KeyError):
                pass
        missing = sorted(set(reference) - set(finals))
        mismatched = sorted(o for o in finals
                            if reference.get(o) != finals[o])
        result["lineages_reported"] = len(finals)
        result["lineages_missing"] = missing
        result["lineages_mismatched"] = mismatched
        result["bit_identical"] = not missing and not mismatched
        if arm == "peer":
            doc = rendezvous.statusz_snapshot()
            rec = doc.get("recovery") or {}
            result["statusz_recovery_reports"] = rec.get("reports", 0)
        result["ok"] = True
        return result
    except Exception as e:
        gang.kill_all()  # before reading rank 0's pipe: a live worker
        out = ""         # would block the read forever
        if gang.rank0_out is not None:
            try:
                out = gang.rank0_out.communicate(timeout=10)[0] or ""
            except Exception:
                pass
        result["ok"] = False
        result["error"] = (f"{e}\n--- rank0 output ---\n{out[-3000:]}"
                           f"\n{gang.crash_logs()}")
        return result
    finally:
        gang.kill_all()
        driver.stop()
        rendezvous.stop()


def capture(out_path, smoke=False):
    import tempfile

    if smoke:
        base = {"np": 16, "hosts": 4, "numel": 128, "total_steps": 60,
                "kill_at_step": 34, "ckpt_every": 25, "replicas": 2,
                "step_sleep": 0.05, "cycle_ms": 2, "push_sec": 0.8}
        timeout = 420
    else:
        # checkpoint cadence: 200 steps between checkpoints vs a
        # commit+replication EVERY step — the real-world shape (a
        # checkpoint costs serialize+IO minutes apart; replication is
        # an in-memory exchange), scaled to simulation step time. The
        # kill lands ~198 steps past the last checkpoint, so the
        # baseline replays what its cadence cost it.
        base = {"np": 128, "hosts": 16, "numel": 256,
                "total_steps": 410, "kill_at_step": 398,
                "ckpt_every": 200, "replicas": 2, "step_sleep": 0.1,
                "cycle_ms": 2, "push_sec": 1.0}
        timeout = 1500
    record = {"schema": SCHEMA, "mode": "smoke" if smoke else "full",
              "spec": dict(base), "configs": [], "claims": {}}
    results = {}
    for arm in ("restore", "peer"):
        spec = dict(base)
        if arm == "restore":
            spec["ckpt_dir"] = tempfile.mkdtemp(prefix="hvt_er_ckpt_")
        t0 = time.monotonic()
        res = run_arm(arm, spec, timeout=timeout)
        res["total_sec"] = round(time.monotonic() - t0, 1)
        results[arm] = res
        record["configs"].append(res)
        print(json.dumps({k: res.get(k) for k in
                          ("arm", "ok", "time_to_recovered_sec",
                           "kv_requests_recovery_total",
                           "bit_identical", "total_sec", "error")}),
              flush=True)
        if not res.get("ok"):
            break

    record["claims"] = build_claims(base, results)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        print(f"wrote {out_path}")
    print("claims: " + json.dumps(record["claims"]))
    return record


def build_claims(base, results):
    """The gated claims, a pure function of the measured arm configs
    (kept separate so a re-gate never needs a re-run)."""
    r, p = results.get("restore", {}), results.get("peer", {})
    if r.get("ok") and p.get("ok"):
        survivors = base["np"] - base["np"] // base["hosts"]

        def round_reqs(res):
            # the per-ROUND report wave: failure + READY. The
            # `recovery` scope is a continuous phase stream (one
            # batched request per host per tick while recovering), so
            # it scales with hosts x duration, not with ranks — it is
            # recorded above but gated separately.
            kr = res["kv_requests_recovery"]
            return kr.get("failure", 0) + kr.get("state", 0)

        return {
            "ranks": base["np"], "hosts": base["hosts"],
            "recovered_both": True,
            "time_to_recovered_restore_sec":
                r["time_to_recovered_sec"],
            "time_to_recovered_peer_sec": p["time_to_recovered_sec"],
            "speedup_x": round(r["time_to_recovered_sec"]
                               / max(p["time_to_recovered_sec"],
                                     1e-9), 2),
            "bit_identical_peer": p["bit_identical"],
            "bit_identical_restore": r["bit_identical"],
            "kv_round_requests_peer": round_reqs(p),
            "kv_round_requests_restore": round_reqs(r),
            "kv_requests_recovery_peer":
                p["kv_requests_recovery_total"],
            "kv_requests_recovery_restore":
                r["kv_requests_recovery_total"],
            # the O(hosts) gate: the relayed arm's per-round report
            # wave is bounded by a PER-HOST constant (8 — detection
            # skew on an oversubscribed sim box spreads a host's
            # burst across several debounce windows; real clusters
            # cluster within one or two), independent of how many
            # ranks each host carries; the direct arm scales with
            # survivors (>= one failure + one READY each)
            "kv_round_requests_peer_bound": 8 * base["hosts"],
            "kv_requests_o_hosts": round_reqs(p) <= 8 * base["hosts"],
            "kv_requests_o_ranks_direct": round_reqs(r) >= survivors,
            "statusz_recovery_rows":
                (p.get("statusz_recovery_reports") or 0) > 0,
        }
    return {"recovered_both": False}


def check(path):
    """Artifact schema + claims validation (ci.sh --elastic). The full
    artifact gates the headline ≥3x time-to-recovered speedup; the
    smoke pair gates ≥1.2x (smaller replay window, shared-box noise)
    plus every structural claim at full strength."""
    with open(path) as f:
        rec = json.load(f)
    errs = []
    if rec.get("schema") != SCHEMA:
        errs.append(f"schema != {SCHEMA}")
    cfgs = rec.get("configs", [])
    arms = {c.get("arm") for c in cfgs}
    if arms != {"restore", "peer"}:
        errs.append(f"configs must cover restore+peer, got {arms}")
    for c in cfgs:
        if not c.get("ok"):
            errs.append(f"arm {c.get('arm')}: not ok: "
                        f"{str(c.get('error'))[:300]}")
        for key in ("time_to_recovered_sec", "bit_identical",
                    "kv_requests_recovery_total"):
            if key not in c:
                errs.append(f"arm {c.get('arm')} missing {key}")
    cl = rec.get("claims") or {}
    if not cl.get("recovered_both"):
        errs.append("claims: recovered_both is not true")
    else:
        floor = 3.0 if rec.get("mode") == "full" else 1.2
        if (cl.get("speedup_x") or 0) < floor:
            errs.append(f"speedup_x {cl.get('speedup_x')} < {floor}")
        for k in ("bit_identical_peer", "bit_identical_restore",
                  "kv_requests_o_hosts", "kv_requests_o_ranks_direct",
                  "statusz_recovery_rows"):
            if cl.get(k) is not True:
                errs.append(f"claim {k} is {cl.get(k)!r}, want true")
    for e in errs:
        print(f"elastic_recovery --check: {e}", file=sys.stderr)
    if errs:
        return 1
    print(f"elastic_recovery --check: OK ({len(cfgs)} arms, claims: "
          f"{json.dumps(cl)})")
    return 0


def main():
    if os.environ.get("HVT_ER_WORKER"):
        _worker()
        return 0
    _stub_package()
    args = sys.argv[1:]

    def argval(flag, dflt):
        if flag not in args:
            return dflt
        i = args.index(flag) + 1
        if i >= len(args):
            sys.exit(f"elastic_recovery: {flag} requires a value")
        return args[i]

    if "--check" in args:
        return check(argval("--check", ""))
    out = argval("--out", "" if "--smoke" in args
                 else os.path.join(REPO, "benchmarks",
                                   "r14_elastic_recovery.json"))
    capture(out, smoke="--smoke" in args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
