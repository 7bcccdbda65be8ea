"""Launcher unit tests (reference ``test/single/test_run.py``: CLI parsing,
command construction, env plumbing — 58 tests there; the same concerns
covered here without mocks where possible)."""

import json
import urllib.request

import pytest

from horovod_tpu.runner.hosts import (HostInfo, get_host_assignments,
                                      parse_hosts)
from horovod_tpu.runner.launch import build_commands, parse_args, slot_env


def test_parse_hosts():
    hs = parse_hosts("a:2, b:4,c")
    assert [(h.hostname, h.slots) for h in hs] == [("a", 2), ("b", 4),
                                                   ("c", 1)]


def test_host_assignments_single_host():
    slots = get_host_assignments([HostInfo("localhost", 4)], 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert [s.local_rank for s in slots] == [0, 1, 2, 3]
    assert all(s.cross_size == 1 and s.cross_rank == 0 for s in slots)


def test_host_assignments_two_hosts():
    slots = get_host_assignments(
        [HostInfo("h1", 2), HostInfo("h2", 2)], 4)
    assert [(s.hostname, s.rank, s.local_rank, s.cross_rank)
            for s in slots] == [("h1", 0, 0, 0), ("h1", 1, 1, 0),
                                ("h2", 2, 0, 1), ("h2", 3, 1, 1)]
    assert all(s.local_size == 2 and s.cross_size == 2 for s in slots)


def test_host_assignments_uneven():
    slots = get_host_assignments([HostInfo("h1", 3), HostInfo("h2", 1)], 4)
    # h2 has no slot at local_rank 1,2 → cross_size differs per local_rank
    by_rank = {s.rank: s for s in slots}
    assert by_rank[0].cross_size == 2  # local_rank 0 exists on both
    assert by_rank[1].cross_size == 1  # local_rank 1 only on h1


def test_oversubscription_rejected():
    with pytest.raises(ValueError, match="exceeds available slots"):
        get_host_assignments([HostInfo("h1", 2)], 3)


def test_parse_args_basic():
    args = parse_args(["-np", "4", "python", "train.py", "--lr", "0.1"])
    assert args.num_proc == 4
    assert args.command == ["python", "train.py", "--lr", "0.1"]
    assert args.backend == "engine"


def test_slot_env_plumbing():
    args = parse_args(["-np", "2", "--timeline", "/tmp/t.json", "python",
                       "x.py"])
    slots = get_host_assignments([HostInfo("localhost", 2)], 2)
    env = slot_env({}, slots[1], args, "127.0.0.1")
    assert env["HVT_PROCESS_ID"] == "1"
    assert env["HVT_NUM_PROCESSES"] == "2"
    assert env["HVT_MASTER_ADDR"] == "127.0.0.1"
    assert env["HVT_TIMELINE"] == "/tmp/t.json"
    assert env["HVT_FUSION_THRESHOLD"] == str(64 << 20)


def test_build_commands_local_vs_ssh():
    args = parse_args(["-np", "2", "python", "x.py"])
    slots = get_host_assignments(
        [HostInfo("localhost", 1), HostInfo("farhost", 1)], 2)
    cmds = build_commands(args, slots, "localhost")
    assert cmds[0][0] == ["python", "x.py"]
    assert cmds[1][0][0] == "ssh"
    assert "farhost" in cmds[1][0]
    joined = " ".join(cmds[1][0])
    assert "HVT_PROCESS_ID=1" in joined


def test_jax_backend_env():
    args = parse_args(["-np", "2", "--backend", "jax", "python", "x.py"])
    slots = get_host_assignments([HostInfo("localhost", 2)], 2)
    env = slot_env({}, slots[0], args, "127.0.0.1")
    assert "HVT_COORDINATOR_ADDR" in env
    assert "HVT_MASTER_ADDR" not in env


def test_engine_slots_sharing_a_host_run_on_the_cpu():
    # a chip belongs to one process and nothing assigns chips to slots:
    # chip hosts export JAX_PLATFORMS themselves, so the pin overrides it
    chip_host = {"JAX_PLATFORMS": "tpu,cpu"}
    engine = parse_args(["-np", "2", "python", "x.py"])
    shared = get_host_assignments([HostInfo("localhost", 2)], 2)
    assert slot_env(chip_host, shared[1], engine,
                    "127.0.0.1")["JAX_PLATFORMS"] == "cpu"
    alone = get_host_assignments(
        [HostInfo("localhost", 1), HostInfo("farhost", 1)], 2)
    assert slot_env(chip_host, alone[0], engine,
                    "127.0.0.1")["JAX_PLATFORMS"] == "tpu,cpu"
    # --backend jax is one process per chip host: its platform stands
    jax_mode = parse_args(["-np", "2", "--backend", "jax", "python",
                           "x.py"])
    assert slot_env(chip_host, shared[0], jax_mode,
                    "127.0.0.1")["JAX_PLATFORMS"] == "tpu,cpu"
    # and the choice reaches remote slots through the ssh command
    cmds = build_commands(engine, get_host_assignments(
        [HostInfo("farhost", 2)], 2), "farhost")
    assert "JAX_PLATFORMS=cpu" in " ".join(cmds[0][0])


def test_rendezvous_server_roundtrip():
    from horovod_tpu.runner.http_server import RendezvousServer

    slots = get_host_assignments([HostInfo("h1", 2)], 2)
    srv = RendezvousServer()
    srv.init(slots)
    port = srv.start(0)
    base = f"http://127.0.0.1:{port}"
    try:
        # slot info
        with urllib.request.urlopen(f"{base}/rendezvous/h1/1") as r:
            info = json.loads(r.read())
        assert info["rank"] == 1 and info["local_size"] == 2
        # world
        with urllib.request.urlopen(f"{base}/world") as r:
            world = json.loads(r.read())
        assert world["size"] == 2 and world["hosts"] == ["h1"]
        # scoped KV
        req = urllib.request.Request(f"{base}/kv/global/addr", data=b"x:1",
                                     method="PUT")
        urllib.request.urlopen(req)
        with urllib.request.urlopen(f"{base}/kv/global/addr") as r:
            assert r.read() == b"x:1"
        with urllib.request.urlopen(f"{base}/keys/global") as r:
            assert json.loads(r.read()) == ["addr"]
        # missing key → 404
        try:
            urllib.request.urlopen(f"{base}/kv/global/nope")
            assert False
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()


def test_launcher_sigkill_leaves_no_orphan_workers(tmp_path):
    """SIGKILL the launcher mid-job: workers must die via PDEATHSIG, not
    leak (reference safe_shell_exec.py:60-140 parent-death contract)."""
    import os
    import signal
    import subprocess
    import sys
    import time

    worker = tmp_path / "worker.py"
    worker.write_text(
        "import os, sys, time\n"
        "print(f'WPID {os.getpid()}', flush=True)\n"
        "time.sleep(120)\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    launcher = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(worker)],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    pids = []
    deadline = time.time() + 30
    while len(pids) < 2 and time.time() < deadline:
        line = launcher.stdout.readline()
        if "WPID" in line:
            pids.append(int(line.rsplit(" ", 1)[1]))
    assert len(pids) == 2, f"workers did not start (got {pids})"
    launcher.kill()  # SIGKILL: launcher gets NO chance to clean up
    launcher.wait(timeout=30)
    deadline = time.time() + 10
    alive = set(pids)
    while alive and time.time() < deadline:
        for pid in list(alive):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                alive.discard(pid)
        time.sleep(0.2)
    for pid in alive:  # cleanup before failing
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    assert not alive, f"orphan workers survived launcher SIGKILL: {alive}"


def test_check_build_reports_capabilities(capsys):
    """hvtrun --check-build (reference runner/launch.py:110): prints the
    capability table without requiring -np, exits 0."""
    from horovod_tpu.runner.launch import main

    assert main(["--check-build"]) == 0
    out = capsys.readouterr().out
    assert "Available Frameworks" in out
    assert "[X] JAX (core)" in out
    assert "XLA/ICI compiled collectives" in out
    # engine is built in this tree (conftest builds it)
    assert "[X] TCP control star" in out
