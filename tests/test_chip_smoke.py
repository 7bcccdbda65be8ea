"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide, section 2.1):
the phases' control flow at tiny sizes, which phases a run is made of, and
the one thing a run without an accelerator must do — fail, naming the
platform it found, and print no result line.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

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


# what no cell of the benchmark decides, in the order it runs
PHASES = {1: ["launcher", "device", "flash8192", "flash256", "mla8192",
              "gdn8192", "kda8192",
              "conv8192", "norms8192", "eager"],
          4: ["device", "ring4", "dryrun4"]}


@pytest.mark.parametrize("chips", [1, 4])
def test_a_run_is_made_of_exactly_these_phases(chips, monkeypatch, capsys):
    """A phase can neither be dropped nor come back (a model phase, say)
    in silence."""
    ran = []
    monkeypatch.setattr(chip_smoke, "run_phase",
                        lambda name, fn, meter=None: ran.append(name))
    monkeypatch.setattr("chipbench.setup_sources.enable_compile_cache",
                        lambda: "/nowhere")
    chip_smoke.main(["--chips", str(chips)])
    assert ran == PHASES[chips]
    assert {name[len("phase_"):] for name in vars(chip_smoke)
            if name.startswith("phase_")} == set(PHASES[1] + PHASES[4])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[0]["phase"] == "start" and lines[-1]["ok"] is True


def test_the_chips_peak_is_the_benchmarks():
    """One table of the chip's peak: the device phase's bound on its
    matmul chain is ``chipbench/peaks.json``'s entry, and no other
    figure for it is written in the script."""
    import inspect

    from chipbench.flops import peaks

    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        table = json.load(f)
    assert peaks("TPU v5 lite") == table["TPU v5 lite"]
    assert ('peaks(devices[0].device_kind)["bf16_flops_per_s"]'
            in inspect.getsource(chip_smoke.phase_device))
    assert "197" not in inspect.getsource(chip_smoke)


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    """The rule the smoke test and the dry run take from the benchmark."""
    from chipbench.setup_sources import enable_compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    try:
        # set: JAX's own handling of the variable is left alone
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before[names[0]]
        # unset: one fixed path in the checkout, never a temporary name
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        for name, value in before.items():
            jax.config.update(name, value)


def test_named_phases_alone_run(monkeypatch):
    ran = []
    monkeypatch.setattr(chip_smoke, "run_phase",
                        lambda name, fn, meter=None: ran.append(name))
    monkeypatch.setattr("chipbench.setup_sources.enable_compile_cache",
                        lambda: "/nowhere")
    chip_smoke.main(["--phases", "gdn8192,flash256"])
    assert ran == ["device", "flash256", "gdn8192"]


def test_chunked_rule_against_the_float32_recurrence():
    # the gdn8192 phase's comparison at a size the CPU can afford: the
    # kernels (interpreted here) and the plain path side by side, at a
    # chunk that divides the sequence and one that does not
    for chunk in (64, 100):
        out = chip_smoke.phase_gdn8192(shape=(1, 256, 2, 4, 16, 16),
                                       chunk=chunk)
        assert out["chunk"] == chunk and not out["kernels_compiled"]
        for path in ("kernels", "plain"):
            assert set(out[path]["rel_l2"]) == {"o", "dq", "dk", "dv", "dg",
                                                "dbeta"}
            assert max(out[path]["rel_l2"].values()) < chip_smoke.BF16_REL_L2
            assert out[path]["ms_forward"] > 0
            assert out[path]["ms_forward_and_backward"] > 0


def test_convolution_kernels_beside_the_plain_body():
    # the conv8192 phase's comparison at a size the CPU can afford: the
    # kernels (interpreted here) and the plain body side by side, both
    # cells' kinds of shape (no bias, a bias), two blocks of positions
    out = chip_smoke.phase_conv8192(shapes=((2, 2048, 32, False),
                                            (1, 32, 40, True)))
    assert out["taps"] == 4 and not out["kernels_compiled"]
    for shape, names in (("2x2048x32", {"o", "dx", "dweight"}),
                         ("1x32x40", {"o", "dx", "dweight", "dbias"})):
        for path in ("kernels", "plain"):
            assert set(out[shape][path]["rel_l2"]) == names
            assert max(out[shape][path]["rel_l2"].values()) \
                < chip_smoke.BF16_REL_L2
            assert out[shape][path]["ms_forward"] > 0
            assert out[shape][path]["ms_forward_and_backward"] > 0


def test_norm_kernels_beside_the_plain_bodies():
    # the norms8192 phase's comparison at a size the CPU can afford: the
    # kernels (interpreted here) and the plain bodies side by side, heads
    # of 128 over two blocks of positions and of 256 over a ragged one
    out = chip_smoke.phase_norms8192(shapes=((1, 1024, 2, 128),
                                             (2, 40, 3, 256)))
    assert not out["kernels_compiled"]
    for shape in ("1x1024x2x128", "2x40x3x256"):
        for path in ("kernels", "plain"):
            for norm, names in (("gated", {"y", "do", "dz", "dw"}),
                                ("l2", {"y", "dx"})):
                got = out[shape][f"{norm}_{path}"]
                assert set(got["rel_l2"]) == names
                assert max(got["rel_l2"].values()) < chip_smoke.BF16_REL_L2
                assert got["ms_forward"] > 0
                assert got["ms_forward_and_backward"] > 0


@pytest.mark.parametrize("shape, rotated", [
    ((1, 64, 4, 2, 16), 0), ((1, 64, 2, 2, 24, 16), 0),
    ((1, 64, 3, 3, 24, 16), 8)],
    ids=["grouped-queries", "24-on-16", "16-and-8-on-16"])
def test_flash_kernel_against_the_float32_formula(shape, rotated):
    # grouped-query heads, a value width apart from the key width and the
    # key's last columns as one rotated key a position (the mla8192
    # phase's two entries), interpreter: the comparison itself at a size
    # the CPU can afford; errors are a few bf16 eps
    out = chip_smoke.flash_kernel_vs_f32(shape, rotated)
    assert set(out["rel_l2"]) == (
        {"o", "dq_n", "dq_r", "dk_n", "dk_r", "dv"} if rotated
        else {"o", "dq", "dk", "dv"})
    assert max(out["rel_l2"].values()) < chip_smoke.BF16_REL_L2


def test_eager_phase_on_the_immediate_path(capsys):
    # through run_phase with the benchmark's meter, as main() runs it
    from chipbench.setup_sources import CompileMeter

    chip_smoke.run_phase("eager", chip_smoke.phase_eager, CompileMeter())
    out = json.loads(capsys.readouterr().out)
    assert out["allreduce"] == 3.5
    assert out["broadcast_parameters_leaves"] == 2
    assert out["compile_seconds"] >= 0 and out["cache_hits"] >= 0
    params = {"w": jax.numpy.ones((4, 4))}
    assert chip_smoke.rel_l2(params, params) == 0.0
