"""bench.py harness validation on the virtual CPU mesh.

The real numbers come from a run on the chip; these tests pin the
harness semantics — measure() produces sane throughput/FLOP estimates on
a multi-device mesh, and main()'s scaling sweep computes per-chip
efficiency relative to the 1-chip run (the BASELINE.md metric of record).
"""

import json
import sys

import numpy as np
import pytest

import bench


def test_measure_multidevice_smoke():
    import jax

    per_chip, total, std, flops_per_img, xla_flops, loss = bench.measure(
        "resnet50", jax.devices()[:2], per_chip_batch=1, num_iters=1,
        num_batches_per_iter=1, dtype_name="fp32", image_size=32)
    assert per_chip > 0
    assert total == pytest.approx(per_chip * 2)
    assert np.isfinite(loss)
    # 32px analytic value: 12.3 GFLOP * (32/224)^2 ≈ 0.25 GFLOP
    assert flops_per_img == pytest.approx(12.3e9 * (32 / 224.0) ** 2)
    # XLA's own count (body once, its conv accounting) lands in the same
    # order of magnitude — a cross-check that the harness wiring is sane
    if xla_flops is not None:
        assert 0.3 * flops_per_img < xla_flops < 10 * flops_per_img


def test_main_scaling_sweep_and_json_schema(monkeypatch, capsys):
    per_chip_by_n = {1: 100.0, 2: 95.0, 4: 90.0, 8: 85.0}

    def fake_measure(model_name, devices, per_chip_batch, num_iters,
                     num_batches_per_iter, dtype_name, image_size=224,
                     norm_impl="tpu", conv0_s2d=False, unroll=1):
        pc = per_chip_by_n[len(devices)]
        return pc, pc * len(devices), 0.0, 12.3e9, 23.5e9, 1.23

    monkeypatch.setattr(bench, "measure", fake_measure)
    monkeypatch.setattr(bench, "calibrate_matmul_tflops", lambda p: 100.0)
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "unused")
    # without --force-cpu a run that finds only the CPU is refused
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit, match="'cpu'"):
        bench.main()
    assert capsys.readouterr().out == ""
    monkeypatch.setattr(sys, "argv", ["bench.py", "--force-cpu", "8"])
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(out)

    assert rec["metric"] == "resnet50_synthetic_img_sec_per_chip"
    # headline number is the all-chips (8-device) per-chip throughput
    assert rec["value"] == 85.0
    assert rec["unit"] == "img/sec/chip"
    assert rec["vs_baseline"] == pytest.approx(
        85.0 / bench.BASELINE_IMG_SEC_PER_DEVICE, rel=1e-3)
    assert rec["calib_tflops"] == 100.0
    # 3 identical interleaved samples → zero spread
    assert rec["calib_spread"] == 0.0
    assert rec["achieved_tflops"] == pytest.approx(
        85.0 * 12.3e9 / 1e12, rel=1e-3)
    assert rec["mfu"] == pytest.approx(rec["achieved_tflops"] / 100.0,
                                       rel=1e-2)
    # 8 virtual devices → sweep over powers of two, efficiency vs n=1
    assert rec["scaling"]["n"] == [1, 2, 4, 8]
    assert rec["scaling"]["efficiency"] == [1.0, 0.95, 0.9, 0.85]
    # the published peak is a property of an accelerator: null on the cpu
    assert rec["mfu_vs_peak"] is None and rec["peak_tflops"] is None
    assert rec["config"]["device_kind"] == "cpu"
    assert "suspect" not in rec


def test_calibration_runs_on_cpu():
    tflops = bench.calibrate_matmul_tflops("cpu")
    assert tflops > 0


def test_peak_comes_from_the_table_by_device_kind():
    from types import SimpleNamespace as Device

    assert bench.peak_bf16_tflops(
        Device(platform="tpu", device_kind="TPU v5 lite")) == 197.0
    assert bench.peak_bf16_tflops(
        Device(platform="cpu", device_kind="cpu")) is None
    # an accelerator that is not in the table is an error, not a default
    with pytest.raises(ValueError, match="TPU v99"):
        bench.peak_bf16_tflops(Device(platform="tpu",
                                      device_kind="TPU v99"))


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    import os

    import jax

    before = jax.config.jax_compilation_cache_dir
    # set: JAX's own handling of the variable is left alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert bench.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before
    # unset: one fixed path in the checkout, never a temporary name
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(os.path.dirname(os.path.abspath(bench.__file__)),
                         ".jax_cache")
    try:
        assert bench.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
