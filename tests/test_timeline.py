"""Timeline tests (reference ``test/parallel/test_timeline.py`` runs a job
with HOROVOD_TIMELINE and validates the JSON)."""

import glob
import json

import numpy as np
import pytest

import horovod_tpu as hvt
from horovod_tpu.utils import timeline


def test_timeline_produces_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "timeline.json")
    hvt.start_timeline(path, mark_cycles=True)
    timeline.negotiate_start("grad/w", "ALLREDUCE")
    timeline.negotiate_end("grad/w")
    timeline.activity_start("grad/w", "MEMCPY_IN_FUSION_BUFFER")
    timeline.activity_end("grad/w")
    timeline.activity_start("grad/b", "XLA_ALLREDUCE")
    timeline.activity_end("grad/b")
    timeline.mark_cycle()
    hvt.stop_timeline()

    with open(path) as f:
        events = json.load(f)
    assert isinstance(events, list) and events
    names = {e.get("args", {}).get("name") for e in events
             if e.get("ph") == "M"}
    assert {"grad/w", "grad/b"} <= names
    assert any(e.get("name") == "NEGOTIATE_ALLREDUCE" for e in events)
    assert any(e.get("name") == "CYCLE_START" for e in events)
    # B/E events must balance per lane
    for tid in {e["tid"] for e in events if e.get("ph") in "BE"}:
        b = sum(1 for e in events if e.get("tid") == tid and e["ph"] == "B")
        e_ = sum(1 for e in events if e.get("tid") == tid and e["ph"] == "E")
        assert b == e_


def test_timeline_arms_xla_profiler_session(tmp_path):
    """SURVEY §5.1: start_timeline() must also open an XLA/PJRT profiler
    session so compiled-path device activity is captured alongside the
    engine control-plane trace — one command, both views."""
    import jax
    import jax.numpy as jnp

    path = str(tmp_path / "tl.json")
    hvt.start_timeline(path)
    # run a compiled step inside the session so the xplane has content
    jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    hvt.stop_timeline()

    # chrome trace written...
    with open(path) as f:
        json.load(f)
    # ...and a populated xplane trace directory next to it
    produced = glob.glob(str(tmp_path / "tl.json.xplane") + "/**/*",
                         recursive=True)
    assert any(p.endswith(".xplane.pb") or "trace" in p.lower()
               for p in produced), produced


def test_mark_cycle_dedicated_lane(tmp_path):
    """Cycle instants must live on their own metadata-named lane with
    the rank's pid — not collide with tensor lane 0 (ISSUE 2
    satellite)."""
    path = str(tmp_path / "cyc.json")
    timeline.start(path, mark_cycles=True, xla_profiler=False, pid=3)
    timeline.activity_start("tensor0", "WORK")
    timeline.activity_end("tensor0")
    timeline.mark_cycle()
    timeline.stop()

    with open(path) as f:
        events = json.load(f)
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    cycle = [e for e in events if e.get("name") == "CYCLE_START"]
    assert cycle, events
    assert lanes[cycle[0]["tid"]] == "CYCLE"
    assert lanes[cycle[0]["tid"]] != lanes[
        [e for e in events if e.get("name") == "WORK"][0]["tid"]]
    # every event carries the rank's pid
    assert {e["pid"] for e in events} == {3}
    # the process is named for chrome's process selector
    assert any(e.get("name") == "process_name"
               and e["args"]["name"] == "rank 3" for e in events)


def test_timeline_flushes_before_close(tmp_path):
    """Crash-safety: events must be readable from the shard while the
    timeline is still recording (periodic flush), so a SIGKILLed worker
    loses at most the last unflushed batch."""
    import time

    path = str(tmp_path / "flush.json")
    timeline.start(path, xla_profiler=False)
    timeline.activity_start("t", "STEP")
    timeline.activity_end("t")
    deadline = time.time() + 5
    events = []
    while time.time() < deadline:
        with open(path) as f:
            events = timeline.parse_trace(f.read())
        if any(e.get("name") == "STEP" for e in events):
            break
        time.sleep(0.05)
    timeline.stop()
    assert any(e.get("name") == "STEP" for e in events), events


def test_timeline_start_stop_idempotent(tmp_path):
    path = str(tmp_path / "t2.json")
    hvt.start_timeline(path)
    hvt.start_timeline(path)  # second start is a no-op (ref: returns DUPLICATE)
    hvt.stop_timeline()
    hvt.stop_timeline()
    assert not timeline.active()


def test_engine_timeline_chrome_trace(tmp_path):
    """2-process engine job with HVT_TIMELINE: the coordinator writes a
    valid chrome trace containing the per-tensor NEGOTIATE and execute
    lifecycle (reference test/parallel/test_timeline.py)."""
    import os

    import pytest

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib = os.path.join(REPO, "horovod_tpu", "csrc", "build",
                       "libhvt_core.so")
    if not os.path.exists(lib):
        pytest.skip("C++ engine not built")
    from tests.test_engine_integration import run_workers

    tl_path = str(tmp_path / "engine_timeline.json")
    run_workers("""
        for i in range(3):
            x = np.full((4,), float(r + 1), np.float32)
            res = np.asarray(hvt.allreduce(x, name=f"t{i}", average=True))
            np.testing.assert_allclose(res, (1 + n) / 2.0)
    """, extra_env={"HVT_TIMELINE": tl_path,
                    "HVT_TIMELINE_MARK_CYCLES": "1"})
    with open(tl_path) as f:
        events = json.load(f)
    assert events, "engine timeline is empty"
    lane_names = {e.get("args", {}).get("name") for e in events
                  if e.get("ph") == "M"}
    assert {"t0", "t1", "t2"} <= lane_names
    assert any(e.get("name") == "NEGOTIATE_ALLREDUCE" for e in events)
    assert any(e.get("name") == "ALLREDUCE" for e in events)
    assert any(e.get("name", "").startswith("RANK_READY_")
               for e in events)
    assert any(e.get("name") == "CYCLE_START" for e in events)
    for tid in {e["tid"] for e in events if e.get("ph") in "BE"}:
        b = sum(1 for e in events if e.get("tid") == tid
                and e["ph"] == "B")
        e_ = sum(1 for e in events if e.get("tid") == tid
                 and e["ph"] == "E")
        assert b == e_, f"unbalanced B/E in lane {tid}"


# ---- host spans on the profiler's clock (docs/timeline.md): what the
# package does between steps, beside the device's lines in one trace

@pytest.fixture(scope="module")
def host_span_names(tmp_path_factory):
    """Names of the host plane's events in one ``jax.profiler`` trace
    around an eager collective, a callback dispatch and a checkpoint."""
    import jax
    from jax.profiler import ProfileData

    from horovod_tpu import checkpoint

    folder = tmp_path_factory.mktemp("host_spans")
    state = {"w": np.ones(3, np.float32)}
    manager = checkpoint.CheckpointManager(str(folder / "ckpt"),
                                           async_save=False)
    jax.profiler.start_trace(str(folder / "trace"))
    try:
        hvt.allreduce(np.ones(4, np.float32), name="host_span_probe")
        hvt.jax.CallbackList([hvt.jax.MetricAverageCallback()]).on_epoch_end(
            0, {"loss": 1.0})
        manager.save(1, state)
        manager.wait()
        manager.restore(1, template=state)
    finally:
        jax.profiler.stop_trace()
        manager.close()
    written, = glob.glob(str(folder / "trace" / "**" / "*.xplane.pb"),
                         recursive=True)
    data = ProfileData.from_file(written)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    return {e.name for line in host.lines for e in line.events}


@pytest.mark.parametrize("span", [
    "hvt_eager/allreduce", "hvt_callback/MetricAverageCallback",
    "hvt_checkpoint/save", "hvt_checkpoint/wait", "hvt_checkpoint/restore"])
def test_host_spans_are_in_the_profilers_trace(host_span_names, span):
    assert span in host_span_names
