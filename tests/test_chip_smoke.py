"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide, section 2.1):
the phases' control flow at tiny sizes, and the one thing a run without
an accelerator must do — fail, naming the platform it found, and print no
result line.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_run_without_an_accelerator_fails_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "found platform 'cpu'" in proc.stdout
    assert '"ok": true' not in proc.stdout
    # the launcher phase ran first and its worker refused the platform
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["phase"] == "launcher" and "failed" in last


def test_device_phase_refuses_the_cpu():
    with pytest.raises(chip_smoke.PhaseFailed, match="platform 'cpu'"):
        chip_smoke.phase_device(1)


def test_a_failed_phase_ends_the_run_without_a_result_line(capsys):
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.run_phase("device", lambda: chip_smoke.phase_device(1))
    assert exit_info.value.code == 1
    out = capsys.readouterr().out
    assert json.loads(out)["failed"].startswith("PhaseFailed")
    assert '"ok": true' not in out


def test_flash_must_be_the_compiled_kernel():
    # on the CPU the kernel is interpreted: the gpt phase may not pass
    with pytest.raises(chip_smoke.PhaseFailed, match="interpret"):
        chip_smoke.require_compiled_flash("... tpu_custom_call ...")


def test_resnet_phase_control_flow(monkeypatch):
    """phase_resnet50 over a stand-in job with the same state layout
    (params, batch_stats, opt_state), built on bench's own block."""

    def tiny_job(model_name, devices, batch, steps, dtype, image_size):
        def train_step(params, stats, opt_state, x):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((x @ p["w"] - 1.0) ** 2))(params)
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, g)
            stats = {"mean": 0.9 * stats["mean"] + 0.1 * x.mean(0)}
            return (params, stats, opt_state + 1), loss

        state = ({"w": jnp.zeros((4, 2))}, {"mean": jnp.zeros(4)},
                 jnp.int32(0))
        block = bench._multi_step_block(train_step, 3, steps, 1)
        return bench.TrainJob(block, state, (jnp.ones((batch, 4)),), None)

    monkeypatch.setattr(bench, "resnet_job", tiny_job)
    out = chip_smoke.phase_resnet50(jax.devices()[:1], batch=2,
                                    image_size=8)
    assert out["steps"] == 6 and len(out["losses"]) == 3
    assert out["losses"][-1] < out["losses"][0] < 1.0
    assert out["kernels_changed"] == "1/1"
    assert out["batch_stat_leaves_changed"] == "1/1"

    # a job whose parameters stand still does not pass
    def frozen_job(*args, **kwargs):
        job = tiny_job(*args, **kwargs)
        frozen = jax.jit(lambda p, s, o, x: (p, s, o, jnp.float32(1.0)))
        return job._replace(block=frozen)

    monkeypatch.setattr(bench, "resnet_job", frozen_job)
    with pytest.raises(chip_smoke.PhaseFailed, match="0 of 1 kernels"):
        chip_smoke.phase_resnet50(jax.devices()[:1], batch=2,
                                  image_size=8)


def test_gpt_job_trains_at_a_tiny_sequence():
    # bench.gpt_job as the gpt phase calls it (12 x 768, vocabulary 32768)
    out = chip_smoke.gpt_train(jax.devices()[:1], 16, 1, use_flash=False,
                               dtype="fp32", steps_per_block=1, calls=2)
    assert out["steps"] == 2
    # ln(32768) = 10.4 at initialisation, falling on a repeated batch
    assert 9.0 < out["losses"][0] < 12.0 and out["losses"][1] < \
        out["losses"][0]


def test_flash_kernel_against_the_float32_formula():
    # grouped-query heads, interpreter: the comparison itself at a size
    # the CPU can afford; errors are a few bf16 eps
    out = chip_smoke.flash_kernel_vs_f32((1, 64, 4, 2, 16))
    assert set(out["rel_l2"]) == {"o", "dq", "dk", "dv"}
    assert max(out["rel_l2"].values()) < chip_smoke.BF16_REL_L2


def test_eager_phase_on_the_immediate_path():
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4)}
    out = chip_smoke.phase_eager(params)
    assert out["allreduce"] == 3.5
    assert out["broadcast_parameters_leaves"] == 2
    np.testing.assert_array_equal(
        np.asarray(chip_smoke.rel_l2(params, params)), 0.0)
