"""The six readers of a run's set-up by phase (``startup_split.py``)
against a recorder filled by hand, and in a rehearsal of the command."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from chipbench import startup_split
from chipbench.setup_sources import CHECKOUT
from horovod_tpu.metrics import startup

SIX = ("init_s", "devices_s", "trace_s", "lower_s", "cache_read_s",
       "setup_unnamed_s")
T0 = 1_000_000.0
RUN = {"setup_seconds": 30.0}


def reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}").read


@pytest.fixture
def recorder(monkeypatch):
    """A start of 30 s: import 2, init 10 (devices 9), a step traced for
    4 s with a kernel traced inside it for 1, lowered for 3 with a second
    of that inside a kernel's own trace, read from the cache in 2 of
    2.5 s; then, after the cut, a check's program."""
    rec = startup.Recorder()
    monkeypatch.setattr(startup, "_recorder", rec)
    rec.add_phase("import", None, T0, T0 + 2)
    rec.add_phase("devices", "init", T0 + 2.5, T0 + 11.5)
    rec.add_phase("init", None, T0 + 2, T0 + 12)
    for span in [("trace", "_fwd_call", T0 + 14, T0 + 15),
                 ("trace", "step", T0 + 13, T0 + 17),
                 ("trace", "body", T0 + 18, T0 + 19),
                 ("lower", "jit(step)", T0 + 17, T0 + 20),
                 ("cache_read", None, T0 + 20.25, T0 + 22.25),
                 ("backend_compile", "jit(step)", T0 + 20, T0 + 22.5),
                 ("trace", "reference", T0 + 40, T0 + 45),
                 ("backend_compile", "jit(reference)", T0 + 45, T0 + 50)]:
        rec.add_span(*span)
    return rec


def test_each_reader_gives_its_number(recorder):
    got = {name: reader(name)(None, RUN) for name in SIX}
    assert got == pytest.approx({
        "init_s": 10, "devices_s": 9, "trace_s": 4 + 1, "lower_s": 3 - 1,
        "cache_read_s": 2, "setup_unnamed_s": 30 - 2 - 10 - 5 - 2 - 2.5})


@pytest.mark.parametrize("name", SIX)
def test_none_from_an_empty_recorder(monkeypatch, name):
    monkeypatch.setattr(startup, "_recorder", startup.Recorder())
    assert reader(name)(None, RUN) is None


@pytest.mark.parametrize("name", SIX)
def test_none_from_a_program_without_a_recorder(monkeypatch, name):
    # the parent of PR 35, under this PR's benchmark files
    import horovod_tpu.metrics

    monkeypatch.setitem(sys.modules, "horovod_tpu.metrics.startup", None)
    monkeypatch.delattr(horovod_tpu.metrics, "startup")
    assert reader(name)(None, RUN) is None


def test_the_phases_tile_set_up(recorder):
    found = startup_split.split(RUN)
    assert (found["import_s"] + found["init_s"] + found["trace_s"]
            + found["lower_s"] + found["backend_compile_s"]
            + found["cache_read_s"] + found["setup_unnamed_s"]
            ) == pytest.approx(RUN["setup_seconds"], abs=1e-9)
    assert found["devices_s"] <= found["init_s"]
    # what the harness's meter reads from outside
    assert found["backend_compile_s"] + found["cache_read_s"] \
        == pytest.approx(2.5)


def test_spans_after_the_cut_are_left_out(recorder):
    whole = startup.report()["stages"]
    assert whole["trace"]["seconds"] == pytest.approx(5 + 5)
    assert whole["backend_compile"]["seconds"] == pytest.approx(0.5 + 5)
    found = startup_split.split(RUN)
    assert found["trace_s"] == pytest.approx(5)
    assert found["backend_compile_s"] == pytest.approx(0.5)
    # a longer set-up takes them in
    longer = startup_split.split({"setup_seconds": 60.0})
    assert longer["trace_s"] == pytest.approx(10)


def test_a_rehearsal_reports_all_six_and_they_tile():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "resnet50-b256", "--seed", "5", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    metrics = {k: v["value"] for k, v in json.loads(lines[-1])
               ["metrics"].items()}
    assert set(SIX) <= set(metrics)
    assert metrics["devices_s"] <= metrics["init_s"]
    assert metrics["cache_read_s"] <= metrics["compile_s"] + 0.1
    assert all(metrics[name] >= 0 for name in SIX)
    setup = json.loads(lines[-2])["setup_seconds"]
    assert metrics["init_s"] + metrics["trace_s"] + metrics["lower_s"] \
        + metrics["setup_unnamed_s"] < setup
