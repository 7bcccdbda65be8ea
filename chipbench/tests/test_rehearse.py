"""The command end to end at the families' tiny sizes: on one CPU device
and on four virtual ones, side by side (each is its own process, as every
run of the benchmark is)."""

import json
import os
import subprocess
import sys

from chipbench.setup_sources import CHECKOUT


def start(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, "-m", "chipbench.run", "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", str(trace),
         "--rehearse"],
        cwd=CHECKOUT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def finish(proc):
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err[-3000:]
    lines = out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_on_one_and_on_four_devices():
    runs = {"one": start("gpt2l-s4096", 0), "image": start("resnet50-b256", 1),
            "four": start("gpt2l-dp4", 0)}
    results = {k: finish(p) for k, p in runs.items()}
    for name, (result, lines) in results.items():
        assert set(result) == {"correct", "attempted", "failed", "metrics",
                               "device"}, name
        assert result["correct"] is False       # a rehearsal never counts
        assert result["failed"] == 0 and result["attempted"] > 2
        assert result["device"]["platform"] == "cpu"
        assert not [l for l in lines if "FAILED" in l], lines
        assert any("step_loss_vs_reference: ok" in l for l in lines)
        for metric in result["metrics"].values():
            assert set(metric) == {"value", "unit"}
    assert results["four"][0]["device"]["count"] == 4
    assert {"tok_s_chip", "hbm_step", "setup_s"} <= set(
        results["four"][0]["metrics"])
    assert "img_s_chip" not in results["one"][0]["metrics"]
    assert any("parameters_bit_identical_on_4_chips: ok" in l
               for l in results["four"][1])
    assert any("reduced_gradient_norm_vs_pmean: ok" in l
               for l in results["four"][1])
    # traced, off the chip: no device plane, so only what needs no trace
    assert set(results["image"][0]["metrics"]) <= {"compile_s",
                                                   "hbm_reserved"}


def test_refuses_the_cpu_without_rehearse():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "gpt2l-s1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "{" not in proc.stdout
