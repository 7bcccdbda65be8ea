"""TPU preemption hook (SURVEY §5.3): SIGTERM / maintenance notices become
HostsUpdatedInterrupt at the next commit, driving the elastic reset path.

Unit tests exercise the watcher directly; the integration test delivers a
real SIGTERM to a worker mid-epoch under an elastic hvtrun launch and
asserts commit→interrupt→reset→resume with stable ranks."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

import horovod_tpu as hvt
from horovod_tpu.elastic import ObjectState, preemption
from horovod_tpu.elastic.preemption import PreemptionWatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "horovod_tpu", "csrc", "build", "libhvt_core.so")


@pytest.fixture(autouse=True)
def _clean_watcher():
    preemption._reset_for_tests()
    yield
    preemption._reset_for_tests()


def fields_of(line):
    """Parse 'BATCH slot=.. rank=..'-style worker lines (launcher output
    prefixes each line with '[rank] ', which carries no '=')."""
    return dict(kv.split("=") for kv in line.split() if "=" in kv)


def wait_until(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def test_sigterm_flags_states_and_commit_raises():
    state = ObjectState(epoch=0)
    w = PreemptionWatcher()
    w.watch(state)
    prev = signal.getsignal(signal.SIGTERM)
    w.install()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        assert wait_until(lambda: w.triggered)
        with pytest.raises(hvt.HostsUpdatedInterrupt):
            state.commit()
        state.commit()  # notice consumed
    finally:
        w.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_maintenance_poll_fn_triggers():
    state = ObjectState(epoch=0)
    pending = {"flag": False}
    w = PreemptionWatcher(poll_fn=lambda: pending["flag"],
                          poll_interval=0.01)
    w.watch(state)
    w.install()
    try:
        time.sleep(0.05)
        assert not w.triggered
        pending["flag"] = True
        assert wait_until(lambda: w.triggered)
        with pytest.raises(hvt.HostsUpdatedInterrupt):
            state.commit()
    finally:
        w.uninstall()


def test_elastic_run_resumes_after_preemption_notice():
    calls = {"n": 0}
    w = PreemptionWatcher()

    @hvt.elastic.run
    def train(state):
        calls["n"] += 1
        if calls["n"] == 1:
            w.trigger("maintenance-event")
            state.commit()  # raises HostsUpdatedInterrupt
        return state.epoch

    s = ObjectState(epoch=4)
    w.watch(s)
    assert train(s) == 4
    assert calls["n"] == 2


def test_watch_state_gating(monkeypatch):
    s = ObjectState(epoch=0)
    monkeypatch.delenv("HVT_RENDEZVOUS_ADDR", raising=False)
    monkeypatch.delenv("HVT_PREEMPTION_WATCH", raising=False)
    assert preemption.watch_state(s) is None  # not an elastic launch
    monkeypatch.setenv("HVT_PREEMPTION_WATCH", "1")
    w = preemption.watch_state(s)
    assert w is not None and w.installed
    monkeypatch.setenv("HVT_PREEMPTION_WATCH", "0")
    preemption._reset_for_tests()
    assert preemption.watch_state(s) is None  # explicit opt-out


def test_watcher_reports_driver_kv(monkeypatch):
    """The preempt notice lands in the rendezvous KV and the driver hook
    broadcasts a host-update to registered workers."""
    from horovod_tpu.runner.elastic.notification import \
        WorkerNotificationManager
    from horovod_tpu.runner.http_server import RendezvousServer

    rendezvous = RendezvousServer()
    rendezvous.start()
    notified = []

    class FakeDriver:
        def _on_kv_put(self, scope, key, value):
            if scope == "preempt":
                notified.append(key)

    rendezvous.set_put_hook(FakeDriver()._on_kv_put)
    try:
        monkeypatch.setenv("HVT_RENDEZVOUS_ADDR",
                           f"127.0.0.1:{rendezvous.port}")
        monkeypatch.setenv("HVT_HOSTNAME", "host-a")
        monkeypatch.setenv("HVT_LOCAL_PROCESS_ID", "1")
        w = PreemptionWatcher()
        w.trigger("signal:15")
        assert wait_until(lambda: notified)
        assert notified[0] == "host-a/1"
    finally:
        rendezvous.stop()


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built (make -C horovod_tpu/csrc)")
def test_sigterm_worker_midepoch_resumes_with_stable_ranks(tmp_path):
    """End-to-end: elastic 2-proc job, SIGTERM one worker mid-epoch →
    both workers interrupt at commit, re-rendezvous, resume from the
    committed batch with the same (slot → rank) mapping, and finish."""
    marker_dir = str(tmp_path)
    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import horovod_tpu as hvt
        from horovod_tpu.elastic import ObjectState

        TMP = {marker_dir!r}
        TOTAL = 6

        # spawn slot, captured BEFORE the elastic runner rewrites
        # HVT_LOCAL_PROCESS_ID per round — labels the PROCESS, so the
        # slot→rank stability assertion is real
        slot = os.environ.get("HVT_LOCAL_PROCESS_ID", "0")

        @hvt.elastic.run
        def train(state):
            with open(f"{{TMP}}/pid_{{slot}}", "w") as f:
                f.write(str(os.getpid()))
            while state.batch < TOTAL:
                hvt.allreduce(np.float32(1.0), name=f"b{{state.batch}}")
                print(f"BATCH slot={{slot}} rank={{hvt.process_rank()}}"
                      f" size={{hvt.process_size()}}"
                      f" batch={{state.batch}}", flush=True)
                open(f"{{TMP}}/progress_{{slot}}_{{state.batch}}",
                     "w").close()
                state.batch += 1
                time.sleep(0.25)
                state.commit()
            print(f"DONE slot={{slot}} rank={{hvt.process_rank()}}"
                  f" batch={{state.batch}}", flush=True)

        hvt.init()
        train(ObjectState(batch=0))
        hvt.shutdown()
    """)
    path = os.path.join(marker_dir, "worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--min-np", "2", "--master-port", "29810",
         sys.executable, path],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        # wait until both workers committed a couple of batches
        assert wait_until(
            lambda: os.path.exists(f"{marker_dir}/progress_0_1")
            and os.path.exists(f"{marker_dir}/progress_1_1"), timeout=60), \
            "workers never reached batch 1"
        with open(f"{marker_dir}/pid_1") as f:
            pid = int(f.read())
        os.kill(pid, signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    except Exception:
        proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        raise AssertionError(f"elastic job did not complete:\n{out}")
    assert proc.returncode == 0, f"rc={proc.returncode}\n{out}"
    # both workers finished all batches
    assert "DONE slot=0" in out and "DONE slot=1" in out, out
    # ranks stayed stable across the preemption round: every slot keeps
    # one rank for the whole job
    slot_ranks = {}
    batches_1 = []
    for line in out.splitlines():
        if "BATCH " in line or "DONE " in line:
            fields = fields_of(line)
            slot_ranks.setdefault(fields["slot"], set()).add(fields["rank"])
            if "BATCH " in line and fields["slot"] == "1":
                batches_1.append(int(fields["batch"]))
    assert set(slot_ranks) == {"0", "1"}, out
    for slot, ranks in slot_ranks.items():
        assert len(ranks) == 1, f"slot {slot} changed rank: {ranks}\n{out}"
    # the signaled worker went through interrupt → reset → resume: its
    # batch counter must not restart from 0 after the first commit
    assert sorted(set(batches_1)) == list(range(6)), batches_1


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built (make -C horovod_tpu/csrc)")
def test_worker_death_restores_tf_keras_state(tmp_path):
    """Elastic TF job: kill a worker mid-run; survivors restore from
    their commit, the respawned worker syncs weights from rank 0, and
    the final model state is exactly TOTAL deterministic updates on
    every rank (reference tensorflow/elastic.py semantics)."""
    marker_dir = str(tmp_path)
    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import horovod_tpu as hvt
        hvt.init()
        import tensorflow as tf
        import horovod_tpu.tensorflow.elastic as tfe

        TMP = {marker_dir!r}
        TOTAL = 6
        v = tf.Variable([100.0])
        model = tf.keras.Sequential()  # state rides the explicit var list
        state = tfe.TensorFlowState([v], batch=0)

        # spawn slot, captured BEFORE the elastic runner rewrites
        # HVT_LOCAL_PROCESS_ID per round — labels the PROCESS, so the
        # slot→rank stability assertion is real
        slot = os.environ.get("HVT_LOCAL_PROCESS_ID", "0")

        @hvt.elastic.run
        def train(state):
            with open(f"{{TMP}}/pid_{{slot}}", "w") as f:
                f.write(str(os.getpid()))
            while state.batch < TOTAL:
                hvt.allreduce(np.float32(1.0), name=f"b{{state.batch}}")
                v.assign_sub([1.0])       # deterministic update per batch
                state.batch += 1
                open(f"{{TMP}}/tfprog_{{slot}}_{{state.batch}}",
                     "w").close()
                time.sleep(0.25)
                state.commit()
            print(f"TFDONE slot={{slot}} w={{float(v.numpy()[0])}}",
                  flush=True)

        train(state)
        hvt.shutdown()
    """)
    path = os.path.join(marker_dir, "tf_worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                "TF_CPP_MIN_LOG_LEVEL": "3"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--min-np", "2", "--master-port", "29812",
         sys.executable, path],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        assert wait_until(
            lambda: os.path.exists(f"{marker_dir}/tfprog_0_2")
            and os.path.exists(f"{marker_dir}/tfprog_1_2"), timeout=120), \
            "workers never reached batch 2"
        with open(f"{marker_dir}/pid_1") as f:
            pid = int(f.read())
        os.kill(pid, signal.SIGTERM)
        out, _ = proc.communicate(timeout=240)
    except Exception:
        proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        raise AssertionError(f"elastic TF job did not complete:\n{out}")
    assert proc.returncode == 0, f"rc={proc.returncode}\n{out}"
    # both finished with EXACTLY TOTAL applied updates — rollback/sync
    # must not lose or double-apply any
    finals = [line for line in out.splitlines() if "TFDONE" in line]
    assert len(finals) == 2, out
    for line in finals:
        assert "w=94.0" in line, line


@pytest.mark.skipif(not os.path.exists(LIB),
                    reason="C++ engine not built (make -C horovod_tpu/csrc)")
def test_grown_host_gets_worker_at_next_rendezvous(tmp_path):
    """End-to-end growth (VERDICT r2 #8; reference
    elastic_common.py:34-60): a discovery script flips localhost:2 →
    localhost:3 mid-job. Running workers interrupt at the next commit,
    re-rendezvous, the NEW slot receives a worker in that round, the
    surviving slots keep their ranks, and everyone finishes with
    size == 3."""
    marker_dir = str(tmp_path)
    disc = os.path.join(marker_dir, "discover.sh")
    with open(disc, "w") as f:
        f.write(textwrap.dedent(f"""\
            #!/bin/sh
            if [ -f {marker_dir}/grow ]; then
                echo localhost:3
            else
                echo localhost:2
            fi
        """))
    os.chmod(disc, 0o755)
    script = textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import horovod_tpu as hvt
        from horovod_tpu.elastic import ObjectState

        TMP = {marker_dir!r}
        TOTAL = 8

        # spawn slot, captured BEFORE the elastic runner rewrites
        # HVT_LOCAL_PROCESS_ID per round (labels the process, not the
        # round's local rank)
        slot = os.environ.get("HVT_LOCAL_PROCESS_ID", "0")

        @hvt.elastic.run
        def train(state):
            while state.batch < TOTAL:
                hvt.allreduce(np.float32(1.0), name=f"b{{state.batch}}")
                print(f"BATCH slot={{slot}} rank={{hvt.process_rank()}}"
                      f" size={{hvt.process_size()}}"
                      f" batch={{state.batch}}", flush=True)
                open(f"{{TMP}}/progress_{{slot}}_{{state.batch}}",
                     "w").close()
                state.batch += 1
                time.sleep(0.3)
                state.commit()
            print(f"DONE slot={{slot}} rank={{hvt.process_rank()}}"
                  f" size={{hvt.process_size()}}", flush=True)

        hvt.init()
        train(ObjectState(batch=0))
        hvt.shutdown()
    """)
    path = os.path.join(marker_dir, "worker.py")
    with open(path, "w") as f:
        f.write(script)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--min-np", "2", "--max-np", "3",
         "--host-discovery-script", disc, "--master-port", "29814",
         sys.executable, path],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        assert wait_until(
            lambda: os.path.exists(f"{marker_dir}/progress_0_1")
            and os.path.exists(f"{marker_dir}/progress_1_1"), timeout=60), \
            "workers never reached batch 1"
        open(f"{marker_dir}/grow", "w").close()  # flip discovery 2 → 3
        out, _ = proc.communicate(timeout=150)
    except Exception:
        proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        raise AssertionError(f"elastic growth job did not complete:\n{out}")
    assert proc.returncode == 0, f"rc={proc.returncode}\n{out}"

    slot_ranks, sizes_by_slot = {}, {}
    for line in out.splitlines():
        if "BATCH " in line or "DONE " in line:
            fields = fields_of(line)
            slot_ranks.setdefault(fields["slot"], set()).add(fields["rank"])
            if "size" in fields:
                sizes_by_slot.setdefault(fields["slot"], []).append(
                    int(fields["size"]))
    # the grown slot actually received a worker at the next round
    assert "2" in slot_ranks, f"new slot never started: {slot_ranks}\n{out}"
    assert "DONE slot=2" in out, out
    # every slot finished at world size 3
    for slot, sizes in sizes_by_slot.items():
        assert sizes[-1] == 3, f"slot {slot} final size {sizes[-1]}\n{out}"
    # surviving slots kept their ranks across the growth round
    for slot in ("0", "1"):
        assert len(slot_ranks[slot]) == 1, \
            f"slot {slot} changed rank: {slot_ranks[slot]}\n{out}"
