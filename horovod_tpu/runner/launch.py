"""``hvtrun`` — the launcher CLI (reference ``horovod/runner/launch.py``:
parse_args:242, _run_static:527, run_controller:675).

Usage:
    python -m horovod_tpu.runner.launch -np 4 python train.py
    hvtrun -np 8 -H host1:4,host2:4 python train.py

Local slots run as direct subprocesses; remote hosts are reached over ssh
with the slot env inlined (reference gloo_run.py:65-145 builds the same
per-slot env + ssh command). The engine rendezvous is a TCP control star on
``--master-port`` of the first host, replacing the reference's HTTP-store
rendezvous for static jobs; elastic jobs use the HTTP rendezvous server
(``runner/http_server.py``).
"""

from __future__ import annotations

import argparse
import os
import shlex
import socket
import sys

from horovod_tpu.runner import safe_exec
from horovod_tpu.runner.hosts import (get_host_assignments, parse_hostfile,
                                      parse_hosts, pin_engine_gang_to_cpu)

_LOCAL_NAMES = ("localhost", "127.0.0.1")


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="hvtrun",
        description="Launch a horovod_tpu job (CPU engine processes or one "
                    "process per TPU host).")
    # not required at the argparse level so `hvtrun --check-build`
    # answers alone; main() enforces it for actual launches
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="total number of processes (required unless "
                        "--check-build)")
    p.add_argument("-H", "--hosts", default=None,
                   help="host1:slots,host2:slots (default: localhost:np)")
    p.add_argument("--hostfile", default=None,
                   help="hostfile with 'host slots=N' lines")
    p.add_argument("--master-port", type=int, default=29510,
                   help="engine control-plane port on the first host")
    p.add_argument("--ssh-port", type=int, default=22)
    p.add_argument("--cycle-time-ms", type=int, default=2,
                   help="engine cycle time (reference HOROVOD_CYCLE_TIME)")
    p.add_argument("--fusion-threshold-mb", type=int, default=64,
                   help="tensor fusion buffer threshold "
                        "(reference HOROVOD_FUSION_THRESHOLD)")
    p.add_argument("--timeline", default=None,
                   help="chrome-trace timeline output path "
                        "(reference HOROVOD_TIMELINE)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve Prometheus GET /metrics from every worker "
                        "at this base port (worker rank r binds "
                        "port+r); 0 binds ephemeral ports")
    p.add_argument("--stall-warning-sec", type=int, default=60,
                   help="stall inspector warning threshold")
    p.add_argument("--ctrl-topology", choices=["star", "tree"],
                   default=None,
                   help="control-plane shape (HVT_CTRL_TOPOLOGY): tree "
                        "elects one leader per host to aggregate "
                        "negotiation frames, capping rank 0's fan-in at "
                        "the host count (docs/performance.md "
                        "§control-plane); star is the default. The "
                        "launcher sets it for every worker — the value "
                        "must agree gang-wide")
    p.add_argument("--autotune", action="store_true",
                   help="enable Bayesian autotuning of fusion threshold "
                        "and cycle time (reference --autotune)")
    p.add_argument("--autotune-log-file", default=None,
                   help="CSV log of autotune samples "
                        "(reference --autotune-log-file)")
    p.add_argument("--backend", choices=["engine", "jax"], default="engine",
                   help="engine: C++ TCP collectives (CPU/eager); jax: "
                        "jax.distributed bring-up (one process per TPU "
                        "host)")
    elastic = p.add_argument_group(
        "elastic", "fault-tolerant launch (reference launch.py:392 "
        "--min-np/--max-np/--host-discovery-script)")
    elastic.add_argument("--min-np", type=int, default=None,
                         help="minimum world size; enables elastic mode")
    elastic.add_argument("--max-np", type=int, default=None,
                         help="maximum world size (default: -np)")
    elastic.add_argument("--host-discovery-script", default=None,
                         help="executable printing one 'host:slots' per "
                              "line; polled every second")
    elastic.add_argument("--reset-limit", type=int, default=None,
                         help="max re-rendezvous rounds before failing")
    elastic.add_argument("--elastic-timeout", type=float, default=600.0,
                         help="seconds to wait for min-np slots")
    elastic.add_argument("--slots", type=int, default=1,
                         help="default slots per discovered host")
    p.add_argument("--use-mpi", action="store_true",
                   help="launch through a single mpirun command "
                        "(reference run_controller mpi path)")
    p.add_argument("--use-jsrun", action="store_true",
                   help="launch through IBM LSF jsrun")
    p.add_argument("--config-file", default=None,
                   help="YAML file supplying any of these flags; "
                        "explicit CLI flags win (reference --config-file)")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="print available frameworks/controllers/tensor "
                        "operations and exit (-np and a training "
                        "command are not required)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command")
    args = p.parse_args(argv)
    if not args.command and not args.check_build:
        p.error("no training command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if args.config_file:
        from horovod_tpu.runner.config_parser import apply_config

        args = apply_config(args, args.config_file, p)
    return args


def _is_local(hostname: str) -> bool:
    return hostname in _LOCAL_NAMES or hostname == socket.gethostname()


def _ssh_command(env, hostname, ssh_port, command):
    """Build the per-slot ssh command with inline env (reference
    gloo_run.py:114-145). Shared by the static and elastic paths."""
    inline = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in env.items()
        if k.startswith("HVT_")
        or k in ("PATH", "PYTHONPATH", "JAX_PLATFORMS"))
    remote = f"cd {shlex.quote(os.getcwd())} && env {inline} " + \
        " ".join(shlex.quote(c) for c in command)
    return ["ssh", "-o", "StrictHostKeyChecking=no", "-p",
            str(ssh_port), hostname, remote]


def slot_env(base_env, slot, args, master_addr):
    """Per-slot environment (reference gloo_run.py:65-99
    create_slot_env_vars: HOROVOD_RANK/SIZE/LOCAL_RANK/..._ADDR)."""
    from horovod_tpu.runner.hosts import slot_env_vars

    env = dict(base_env)
    env.update(slot_env_vars(slot))
    env.update({
        "HVT_CYCLE_TIME_MS": str(args.cycle_time_ms),
        "HVT_FUSION_THRESHOLD": str(args.fusion_threshold_mb << 20),
        "HVT_STALL_WARN_SEC": str(args.stall_warning_sec),
    })
    if args.backend == "engine":
        env["HVT_MASTER_ADDR"] = master_addr
        env["HVT_MASTER_PORT"] = str(args.master_port)
        pin_engine_gang_to_cpu(env, [slot])
    else:
        env["HVT_COORDINATOR_ADDR"] = f"{master_addr}:{args.master_port}"
    if args.timeline:
        # HVT_TIMELINE: the legacy engine-side rank-0 trace (kept as a
        # fallback surface); HVT_TIMELINE_SHARD: the per-rank flight-
        # recorder shard (<path>.rank<r>) every worker records, uploads
        # to the rendezvous KV, and the launcher merges into <path>
        env["HVT_TIMELINE"] = args.timeline
        env["HVT_TIMELINE_SHARD"] = args.timeline
    if getattr(args, "metrics_port", None) is not None:
        env["HVT_METRICS_PORT"] = str(args.metrics_port)
    if getattr(args, "ctrl_topology", None):
        # must agree across the gang (leaders/members derive from it)
        env["HVT_CTRL_TOPOLOGY"] = args.ctrl_topology
    if getattr(args, "autotune", False):
        env["HVT_AUTOTUNE"] = "1"
        if args.autotune_log_file:
            env["HVT_AUTOTUNE_LOG"] = args.autotune_log_file
    return env


def build_commands(args, slots, master_addr, base_env=None,
                   rendezvous_port=None):
    base_env = dict(os.environ if base_env is None else base_env)
    cmds = []
    for slot in slots:
        env = slot_env(base_env, slot, args, master_addr)
        if rendezvous_port is not None:
            # launcher-side KV server (timeline shard upload, /clock
            # handshake, /debugz); a remote worker must dial the
            # LAUNCHER host, not itself. Deliberately NOT
            # HVT_RENDEZVOUS_ADDR: that var is the "elastic launch"
            # marker (elastic/run.py, preemption.py key off it), and a
            # static --timeline run must not trip those paths.
            host = ("127.0.0.1" if _is_local(slot.hostname)
                    else socket.gethostname())
            env["HVT_DIAG_ADDR"] = f"{host}:{rendezvous_port}"
        if _is_local(slot.hostname):
            cmds.append((list(args.command), env, slot.rank))
        else:
            cmds.append((_ssh_command(env, slot.hostname, args.ssh_port,
                                      args.command),
                         dict(os.environ), slot.rank))
    return cmds


def merge_timeline_shards(timeline_path, store, expected_ranks=()):
    """Merge per-rank timeline shards into ``timeline_path``.

    Shards come from the rendezvous KV (``PUT /kv/timeline/<rank>`` at
    worker teardown); any expected rank missing from the KV falls back
    to its local shard file ``<timeline_path>.rank<r>`` — a SIGKILLed
    worker never uploads, but its flushed shard is still loadable
    (``utils/timeline.py`` crash-safety notes)."""
    from horovod_tpu.utils import timeline as tl

    shards, found = [], set()
    if store is not None:
        for key in store.keys("timeline"):
            v = store.get("timeline", key)
            if v is None:
                continue
            shards.append(tl.parse_trace(v.decode(errors="replace")))
            found.add(str(key))
    missing = []
    for r in expected_ranks:
        if str(r) in found:
            continue
        local = f"{timeline_path}.rank{r}"
        if os.path.exists(local):
            shards.append(tl.load_trace(local))
        else:
            missing.append(r)
    if not shards:
        print(f"[hvtrun] timeline: no shards recorded; {timeline_path} "
              f"not written", file=sys.stderr)
        return 0
    merged = tl.merge_traces(shards)
    import json

    with open(timeline_path, "w") as f:
        json.dump(merged, f)
    note = f" (no shard from ranks {missing})" if missing else ""
    print(f"[hvtrun] timeline: merged {len(shards)} shard(s), "
          f"{len(merged)} events -> {timeline_path}{note}",
          file=sys.stderr)
    return len(shards)


def _run_elastic(args) -> int:
    """Elastic launch: start the ElasticDriver + rendezvous server, spawn
    one training subprocess per assigned slot, restart rounds on host
    changes / failures (reference ``launch.py:619`` _run_elastic)."""
    from horovod_tpu.runner.elastic.discovery import (FixedHostDiscovery,
                                                      HostDiscoveryScript)
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.settings import ElasticSettings
    from horovod_tpu.runner.http_server import RendezvousServer

    if args.host_discovery_script:
        discovery = HostDiscoveryScript(args.host_discovery_script,
                                        default_slots=args.slots)
    elif args.hosts:
        discovery = FixedHostDiscovery(args.hosts)
    else:
        discovery = FixedHostDiscovery(f"localhost:{args.num_proc}")
    settings = ElasticSettings(
        min_np=args.min_np or args.num_proc,
        max_np=args.max_np or args.num_proc,
        elastic_timeout=args.elastic_timeout,
        reset_limit=args.reset_limit, verbose=args.verbose)
    rendezvous = RendezvousServer(verbose=args.verbose)

    def choose_master_port(slots, round_):
        # Engine control-star port for this round, published via world
        # info. When the master slot is on this host, probe a genuinely
        # free port (a fixed rotation window wraps after enough rounds
        # and can collide with a lingering listener from an old round —
        # ADVICE r1); for a remote master fall back to a wide rotation
        # off the configured base.
        if _is_local(slots[0].hostname):
            with socket.socket() as s:
                s.bind(("", 0))
                return s.getsockname()[1]
        return args.master_port + round_ % 2048

    rendezvous.master_port_fn = choose_master_port
    rendezvous_port = rendezvous.start()

    def driver_addr_for(slot_hostname):
        # a remote worker must reach the rendezvous on the *launcher*
        # host, not on itself
        return ("127.0.0.1" if _is_local(slot_hostname)
                else socket.gethostname())

    children = set()
    children_lock = __import__("threading").Lock()

    def create_worker(slot):
        drv_addr = driver_addr_for(slot.hostname)
        mh = (rendezvous.world or {}).get("master_host") or slot.hostname
        master = "127.0.0.1" if _is_local(slot.hostname) and \
            _is_local(mh) else mh
        env = slot_env(dict(os.environ), slot, args, master)
        env["HVT_ELASTIC"] = "1"
        env["HVT_ELASTIC_NOTIFY_ADDR"] = f"{drv_addr}:{rendezvous_port}"
        env["HVT_RENDEZVOUS_ADDR"] = f"{drv_addr}:{rendezvous_port}"
        # per-round engine port, so a worker spawned into round N joins the
        # same control star as survivors re-initializing into round N (see
        # elastic/run.py _apply_slot_env)
        env["HVT_MASTER_PORT_BASE"] = str(args.master_port)
        env["HVT_MASTER_PORT"] = str(
            (rendezvous.world or {}).get("master_port")
            or args.master_port + rendezvous.round % 2048)
        if _is_local(slot.hostname):
            cmd = list(args.command)
        else:
            cmd = _ssh_command(env, slot.hostname, args.ssh_port,
                               args.command)
            env = dict(os.environ)
        child = safe_exec.Child(cmd, env, tag=slot.rank)
        with children_lock:
            children.add(child)
        try:
            return child.wait()
        finally:
            with children_lock:
                children.discard(child)

    def terminate_children():
        with children_lock:
            live = list(children)
        for c in live:
            c.terminate()

    driver = ElasticDriver(rendezvous, discovery, settings,
                           create_worker_fn=create_worker,
                           on_stop=terminate_children)
    # HVT_AUTOSCALE=1: metrics-driven policy loop — scale out on
    # sustained worker backlog, shed/blacklist on failure reports
    # (runner/elastic/autoscaler.py)
    from horovod_tpu.runner.elastic.autoscaler import \
        maybe_start_autoscaler
    autoscaler = maybe_start_autoscaler(driver, rendezvous,
                                        verbose=bool(args.verbose))
    try:
        driver.start(args.num_proc)
        driver.wait()
    finally:
        if autoscaler is not None:
            autoscaler.stop()
        terminate_children()
        if args.timeline:
            # elastic world size varies per round; merge whatever shards
            # workers uploaded (the KV keeps the timeline scope across
            # re-rendezvous resets), with the local-file fallback over
            # the final round's world — elastic is exactly the mode
            # where workers get killed before they can upload
            try:
                final_world = (rendezvous.world or {}).get("size") \
                    or args.num_proc
                merge_timeline_shards(args.timeline, rendezvous.store,
                                      expected_ranks=range(final_world))
            except Exception as e:
                print(f"[hvtrun] timeline merge failed: {e}",
                      file=sys.stderr)
        rendezvous.stop()
    if driver.error:
        print(f"[hvtrun] elastic job failed: {driver.error}",
              file=sys.stderr)
        return 1
    results = driver.get_results()
    bad = {r: rc for r, rc in results.items() if rc != 0}
    if bad:
        print(f"[hvtrun] ranks failed: {sorted(bad.items())}",
              file=sys.stderr)
        return 1
    return 0


def check_build(verbose: bool = False) -> int:
    """Print what this installation can do (reference
    ``runner/launch.py:110`` ``horovodrun --check-build``), recast for
    the TPU stack: framework bindings by importability, the C++ engine
    and TF custom-op library by presence of their built artifacts, and
    the data planes they unlock."""
    import importlib.util
    import os

    def mark(ok):
        return "X" if ok else " "

    def importable(name):
        try:
            return importlib.util.find_spec(name) is not None
        except Exception:
            return False

    from horovod_tpu import __version__
    from horovod_tpu.engine.native import _lib_path

    engine_lib = _lib_path()
    engine = os.path.exists(engine_lib)
    tf_ops = os.path.exists(os.path.join(os.path.dirname(engine_lib),
                                         "libhvt_tf_ops.so"))
    # the Keras wrapper gates on `import tensorflow.keras`
    # (horovod_tpu/keras/__init__.py:_KERAS_AVAILABLE); probing the bare
    # 'tensorflow' spec showed an X for TF builds whose keras shim is
    # broken/absent, so probe the same module the wrapper imports
    keras_ok = importable("tensorflow.keras")
    engine_stats = False
    if engine:
        try:
            from horovod_tpu.engine import native as _native

            engine_stats = bool(_native.engine_stats())
        except Exception:
            engine_stats = False
    out = f"""\
horovod_tpu v{__version__}:

Available Frameworks:
    [X] JAX (core)
    [{mark(importable('tensorflow'))}] TensorFlow
    [{mark(importable('torch'))}] PyTorch
    [{mark(importable('mxnet'))}] MXNet (numpy bridge)
    [{mark(keras_ok)}] Keras

Available Controllers:
    [{mark(engine)}] TCP control star (C++ engine)
    [X] Elastic HTTP rendezvous

Available Tensor Operations:
    [X] XLA/ICI compiled collectives (psum / all_gather / ...)
    [{mark(engine)}] shared-memory local plane
    [{mark(engine)}] TCP ring
    [{mark(engine)}] hierarchical (local RS -> cross AR -> local AG)
    [{mark(tf_ops)}] TF native custom ops

Telemetry:
    [X] Prometheus /metrics registry (hvtrun --metrics-port)
    [{mark(engine_stats)}] engine stats bridge (hvt_engine_stats)"""
    print(out)
    if verbose:
        state = ("present" if engine
                 else "NOT BUILT — run make -C horovod_tpu/csrc")
        print(f"\nengine library: {engine_lib} ({state})")
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    # bound by argparse BEFORE the REMAINDER command, so a
    # --check-build belonging to the training script is not hijacked
    if args.check_build:
        return check_build(verbose=args.verbose)
    if args.num_proc is None:
        print("hvtrun: error: -np/--num-proc is required", file=sys.stderr)
        return 2
    if args.min_np is not None or args.host_discovery_script:
        return _run_elastic(args)
    if args.hostfile:
        hosts = parse_hostfile(args.hostfile)
    elif args.hosts:
        hosts = parse_hosts(args.hosts)
    else:
        hosts = parse_hosts(f"localhost:{args.num_proc}")
    slots = get_host_assignments(hosts, args.num_proc)
    master_addr = ("127.0.0.1" if _is_local(slots[0].hostname)
                   else slots[0].hostname)
    if args.use_mpi:
        from horovod_tpu.runner.mpi_run import mpi_run

        return mpi_run(args, slots, master_addr)
    if args.use_jsrun:
        from horovod_tpu.runner.js_run import js_run

        return js_run(args, slots, master_addr)
    if args.verbose:
        for s in slots:
            print(f"[hvtrun] rank {s.rank} → {s.hostname} "
                  f"(local {s.local_rank}/{s.local_size}, "
                  f"cross {s.cross_rank}/{s.cross_size})", file=sys.stderr)
    rendezvous = None
    rendezvous_port = None
    if args.timeline:
        # static jobs rendezvous over the TCP control star; the timeline
        # still needs an HTTP surface for the clock-offset handshake,
        # shard upload, and GET /debugz — start a KV server for the run
        from horovod_tpu.runner.http_server import RendezvousServer

        rendezvous = RendezvousServer(verbose=args.verbose)
        rendezvous.init(slots)
        rendezvous_port = rendezvous.start()
    try:
        cmds = build_commands(args, slots, master_addr,
                              rendezvous_port=rendezvous_port)
        exit_codes = safe_exec.run_all(cmds)
        if args.timeline:
            try:
                merge_timeline_shards(
                    args.timeline,
                    rendezvous.store if rendezvous else None,
                    expected_ranks=range(args.num_proc))
            except Exception as e:
                # training already finished: a merge failure must not
                # eat the per-rank exit-code report below
                print(f"[hvtrun] timeline merge failed: {e}",
                      file=sys.stderr)
    finally:
        if rendezvous is not None:
            rendezvous.stop()
    bad = [(i, rc) for i, rc in enumerate(exit_codes) if rc != 0]
    if bad:
        print(f"[hvtrun] ranks failed: {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
