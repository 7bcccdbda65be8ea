"""The ``phi4flash`` family: its FLOP and parameter counts against a hand
count and the package's tree; what the scan and the windowed layer require
of a step; its configuration against the catalog's entry and the cell
against its ``BENCHMARK.json`` entry; ``facts`` carrying what the readers
read; the five new readers on a trace without their scopes; the
reference's kinds by layer number; and the cell's rehearsal."""

import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import flops
from chipbench.families import phi4flash
from chipbench.reference import phi4flash as reference
from chipbench.setup_sources import CHECKOUT

CELL = {"seq_len": 16384, "per_chip_batch": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "vocab_size"}
READERS = ("mamba_ms", "mamba_scan_ms", "mamba_scan_roofline", "gmu_ms",
           "attn_diff_ms")


def _json(*parts):
    with open(os.path.join(CHECKOUT, *parts)) as f:
        return json.load(f)


def _config():
    return _json("chipbench/configs/phi4-mini-flash.json")


def test_flops_per_token_of_the_cut_by_hand():
    """The stage at the published widths, a layer of each kind by hand: 826
    M multiply-adds, 4.96 GFLOP a token, 81 TFLOP a step."""
    mlp = 3 * 2560 * 10240
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    q_o, k_v = 2 * 2560 * 2560, 2 * 2560 * 1280
    a_pair = 20 * 2 * (64 + 128)
    assert (mlp, mamba, q_o + k_v, a_pair) == (78_643_200, 41_123_840,
                                               19_660_800, 7_680)
    assert phi4flash.band_pairs(16384, 512) == 8_257_792 == (
        512 * 513 // 2 + (16384 - 512) * 512)
    full = a_pair * 16385 / 2
    windowed = a_pair * 8_257_792 / 16384
    assert full == 62_918_400 and windowed == 3_870_840
    job = phi4flash.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert job.facts["pattern"] == "A-W-A-*-U-X-"
    assert macs == {"A": 2 * mamba, "W": q_o + k_v + windowed,
                    "*": q_o + k_v + full, "X": q_o + full,
                    "U": 2 * 2560 * 5120, "-": 6 * mlp,
                    "head": 25008 * 2560}
    total = sum(macs.values())
    assert total == pytest.approx(826.0e6, abs=0.5e6)
    assert job.flops_per_item == 6.0 * total
    assert job.flops_per_item / 1e9 == pytest.approx(4.96, abs=0.005)
    assert job.items_per_step_per_chip == 16384
    assert job.flops_per_item * 16384 / 1e12 == pytest.approx(81.2, abs=0.1)
    assert macs["-"] / total == pytest.approx(0.57, abs=0.005)
    assert 2 * full / total == pytest.approx(0.15, abs=0.005)


def test_what_the_scan_and_the_window_require_of_a_step():
    """The recurrence: 1.34 G state updates a layer and pass, four
    multiply-adds each, elementwise; its bytes ``u``, ``delta``, ``B``,
    ``C``, ``m`` and their gradients once a pass: 5.04 GB a step, 6.16 ms at
    819 GB/s against 0.44 ms of arithmetic at the MXU's peak, so the bytes
    bound it. The windowed layer: 40 maps over the band's pairs."""
    job = phi4flash.build(_config(), CELL)
    scan = job.facts["mamba"]
    updates = 16384 * 5120 * 16
    assert updates == 1_342_177_280
    assert scan["scan_macs_per_step"] == 2 * updates * 4 * 4
    forward = 5120 * (2 + 4 + 2) + 2 * 16 * 2
    backward = forward + 5120 * (2 + 4) + 2 * 16 * 2
    assert scan["scan_bytes_per_step"] == 2 * 16384 * (2 * forward + backward)
    seconds, bound = flops.roofline_seconds(
        2.0 * scan["scan_macs_per_step"], scan["scan_bytes_per_step"],
        flops.peaks("TPU v5 lite"))
    assert scan["scan_bytes_per_step"] == 5_041_553_408
    assert bound == "memory" and 1e3 * seconds == pytest.approx(6.16, abs=0.01)
    band = job.facts["attn_window"]
    assert band["band_pairs"] == 8_257_792 and band["heads"] == 40
    assert band["macs_per_step"] == 40 * 8_257_792 * 64 * (2 * 3 + 7)
    seconds, bound = flops.roofline_seconds(
        2.0 * band["macs_per_step"], band["bytes_per_step"],
        flops.peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * seconds == pytest.approx(2.79,
                                                                abs=0.01)


def test_parameters_of_the_cut_are_the_trees():
    """697,094,272 parameters, 10.39 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, as the issue counted it."""
    job = phi4flash.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 697_094_272
    assert 16 * size(params) / 2 ** 30 == pytest.approx(10.39, abs=0.005)
    assert extra == {}
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(12)]
    assert kinds == ["mamba", "mlp", "attn", "mlp", "mamba", "mlp", "attn",
                     "mlp", "gmu", "mlp", "cross", "mlp"]
    assert [size(params[f"block_{i}"][kind]) for i, kind in
            ((0, "mamba"), (2, "attn"), (8, "gmu"), (10, "cross"),
             (1, "mlp"))] == [41_241_600, 19_668_864, 26_214_400,
                              13_112_704, 78_643_200]
    assert size(params["block_0"]["norm"]) == 5_120 == size(params["ln_f"])
    assert params["embedding"].shape == (25008, 2560)
    assert "lm_head" not in params
    assert (2 * 119_895_040 + 2 * 98_322_304 + 104_867_840 + 91_766_144
            + 64_020_480 + 5_120) == 697_094_272
    assert job.probe.facts["pattern"] == "WA*UX-"
    assert job.probe.cfg.first_layer == 15
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_3_8b():
    config = {**_config(), "num_hidden_layers": 32, "first_layer": 0,
              "vocab_size": 200064}
    cfg = phi4flash._model_config(config, 16384)
    assert cfg.layer_pattern == ("A-W-" * 8 + "A-*-" + "U-X-" * 7)
    assert phi4flash.n_params(**phi4flash._sizes(cfg)) == (
        9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840 + 7 * 91_766_144
        + 200_064 * 2_560 + 5_120) == 3_852_562_944
    kinds = [reference.layer_kind(l, 32) for l in range(32)]
    assert kinds.count(reference.MAMBA) == 9
    assert kinds.count(reference.WINDOWED) == 8
    assert kinds.count(reference.UNIT) == kinds.count(reference.CROSS) == 7
    assert kinds[14:20] == [reference.MAMBA, reference.WINDOWED,
                            reference.MAMBA, reference.FULL, reference.UNIT,
                            reference.CROSS]


def test_configuration_keeps_the_sources_values():
    """Every value of the catalog's entry under its own key but the two in
    ``reduced``; no width among them; the floors; and what the catalog's
    config has no key for under ``assumed``."""
    config = _config()
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "phi4-mini-flash")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    published = config["published"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert key in config and config[key] == value, key
        assert {k: row["config"][k] for k in REDUCED} == {
            k: published[k] for k in REDUCED}
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["sliding_window"], config["mamba_d_state"],
            config["mamba_dt_rank"], config["mamba_d_conv"],
            config["mamba_expand"]) == (2560, 10240, 40, 20, 512, 16, 160, 4,
                                        2)
    assert config["num_hidden_layers"] == 6 and config["first_layer"] == 14
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("deployment", "departures"):
        assert config[key]
    assert "layers 14 to 19" in config["deployment"]
    assert {"mamba_sizes", "head_pairing", "gated_memory_unit", "positions",
            "sliding_window", "initialisation", "optimizer", "dtype", "remat",
            "seq_len"} <= set(config["assumed"])
    cfg = phi4flash._model_config(config, 16384)
    assert (cfg.layer_pattern, cfg.rotary, cfg.attn_window_rotary,
            cfg.attn_window, cfg.attn_differential, cfg.attn_bias,
            cfg.layer_norm, cfg.first_layer, cfg.head_dim, cfg.norm_eps,
            cfg.tie_embeddings, cfg.mlp_act) == (
                "A-W-A-*-U-X-", False, False, 512, True, True, True, 14, 64,
                1e-5, True, "swiglu")


def test_the_cells_file_is_its_benchmark_entry():
    cell = _json("chipbench/workloads/phi4flash-s16384.json")
    bench = _json("BENCHMARK.json")
    entry = next(w for w in bench["workloads"]
                 if w["name"] == "phi4flash-s16384")
    assert {k: cell[k] for k in ("config", "traffic", "chips")} == {
        k: entry[k] for k in ("config", "traffic", "chips")} == {
            "config": "phi4-mini-flash", "traffic": "b1-s16384", "chips": 1}
    assert (cell["spelling"], cell["per_chip_batch"], cell["seq_len"],
            cell["log_every"]) == ("gspmd", 1, 16384, 1)
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in bench[group]
              if "phi4flash-s16384" in m.get("workloads", [])}
    assert listed == {"tok_s_chip", "recompute_ms", "lm_head_ms",
                      "dense_mlp_ms", "attn_ms", "attn_core_ms",
                      "flash_fwd_ms", "flash_bwd_ms",
                      "attn_window_core_ms", "attn_window_roofline",
                      *READERS}
    for name in READERS:
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        module = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert (metric["unit"], metric["layer"], metric["moves"]) == (
            module.UNIT, module.LAYER, module.MOVES)
        assert metric["workloads"] == ["phi4flash-s16384"]


@pytest.mark.parametrize("key, value", [
    ("model_type", "phi3"), ("tie_word_embeddings", False),
    ("mlp_bias", True), ("mb_per_layer", 4), ("hidden_act", "gelu")])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        phi4flash.build({**_config(), key: value}, CELL)


def test_the_new_readers_read_nothing_where_their_scopes_are_not(monkeypatch):
    """The parent's program has none of the scopes: left out, not 0, and
    nothing raised; so too without a trace."""
    from chipbench import regions, xplane

    for name in READERS:
        module = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert module.read(None, {}) is None
    recorded = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "rehearsal_three_steps.xplane.pb.gz")
    import gzip
    import tempfile

    with tempfile.TemporaryDirectory() as scratch:
        path = os.path.join(scratch, "t.xplane.pb")
        with gzip.open(recorded, "rb") as src, open(path, "wb") as dst:
            dst.write(src.read())
        trace = xplane.load(path)
        monkeypatch.setattr(regions, "trace_file", lambda *a: path)
        run = {"facts": phi4flash.build(_config(), CELL).facts,
               "peak": flops.peaks("TPU v5 lite")}
        for name in READERS:
            assert importlib.import_module(
                f"chipbench.layer_metrics.{name}").read(trace, run) is None


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "phi4flash-s16384", "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is False           # a rehearsal never counts
    assert result["failed"] == 0 and result["attempted"] > 2
    assert not [l for l in lines if "FAILED" in l], lines
    for check in ("step_loss_vs_reference", "grad_vs_reference_WA*UX-",
                  "float32_loss_vs_reference", "float32_grad_vs_reference",
                  "trained_layer_14_mamba_memory", "trained_layer_16_mamba_vs",
                  "trained_layer_15_sliding_attention",
                  "trained_layer_17_full_attention",
                  "trained_layer_18_gated_memory_unit",
                  "trained_layer_19_cross_attention"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {*READERS, "lm_head_ms"}
