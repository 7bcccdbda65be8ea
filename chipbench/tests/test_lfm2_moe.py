"""The ``lfm2_moe`` family: its FLOP and parameter counts against a hand
count and the package's tree; its configuration against the catalog's
rules; ``facts`` carrying what the readers read; its three readers on a
recorded trace whose names are rewritten; and the cell's rehearsal."""

import gzip
import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import flops, regions, xplane
from chipbench.families import lfm2_moe
from chipbench.layer_metrics import moe_experts_roofline
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 8192, "per_chip_batch": 2}
READERS = ("sconv_ms", "sconv_gate_conv_ms", "dense_mlp_ms")


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand; four short convolutions, one attention, the dense MLP, four
    expert layers and the tied head are the cell's 1.22 GFLOP a token."""
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert conv == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    attention = (2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 32 * 64 * 8192)
    assert attention == 8_388_608 + 2_097_152 + 16_777_216
    dense = 3 * 2048 * 11776
    assert dense == 72_351_744
    experts = 2048 * 64 + 4 * 8 / 64 * 3 * 2048 * 1536
    assert experts == 131_072 + 4_718_592
    head = 8192 * 2048
    job = lfm2_moe.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"C": 4 * conv, "*": attention, "-": dense,
                    "E": 4 * experts, "head": head}
    total = 4 * conv + attention + dense + 4 * experts + head
    assert total == 202_924_032
    assert job.flops_per_item == 6.0 * total == 1_217_544_192.0
    assert job.items_per_step_per_chip == 16384
    # the shares of the arithmetic the issue reckoned with: the source's
    # dense layer (a convolution and its MLP) 44%, the MLP alone 36%
    assert (conv + dense) / total == pytest.approx(0.44, abs=0.005)
    assert dense / total == pytest.approx(0.357, abs=0.005)
    assert 4 * conv / total == pytest.approx(0.331, abs=0.005)
    assert 4 * experts / total == pytest.approx(0.096, abs=0.005)
    assert head / total == pytest.approx(0.083, abs=0.005)


def test_facts_carry_what_the_readers_read():
    job = lfm2_moe.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 4, "rows": 8192, "experts": 8, "d_model": 2048,
        "d_expert": 1536, "itemsize": 2, "row_bound": 65536,
        "routed_over": 64}
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "C-*ECECECE"
    least, bound = moe_experts_roofline.least_ms(
        job.facts, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(12.56, abs=0.05)


def test_parameters_of_the_cut_are_the_trees():
    """469.3 M parameters, 6.99 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order, as the issue counted it."""
    job = lfm2_moe.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 469_284_992
    assert 16 * size(params) / 1e9 == pytest.approx(7.51, abs=0.005)
    assert 16 * size(params) / 2 ** 30 == pytest.approx(6.99, abs=0.005)
    assert set(extra["buffers"]) == {"block_3", "block_5", "block_7",
                                     "block_9"}
    assert extra["buffers"]["block_3"]["moe"]["choice_bias"].shape == (64,)
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(10)]
    assert kinds == ["sconv", "mlp", "attn", "moe", "sconv", "moe", "sconv",
                     "moe", "sconv", "moe"]
    assert "lm_head" not in params                  # tied
    mixers = [params[f"block_{i}"][kind]
              for i, kind in ((2, "attn"), (4, "sconv"), (6, "sconv"),
                              (8, "sconv"))]
    assert size(params["block_0"]["sconv"]) == 16_783_360
    assert size(params["block_1"]["mlp"]) == 72_351_744
    assert size((params["block_0"]["sconv"],
                 params["block_1"]["mlp"])) == 89_135_104
    assert size(params["block_2"]["attn"]) == 10_485_888
    assert size(mixers) == 60_835_968
    assert size(params["block_3"]["moe"]) == 75_497_472 + 131_072
    assert size([params[f"block_{i}"]["moe"] for i in (3, 5, 7, 9)]) \
        == 302_514_176
    assert size(params["embedding"]) == 16_777_216
    assert size([params[f"block_{i}"]["norm"] for i in range(10)]
                + [params["ln_f"]]) == 22_528
    moe = params["block_3"]["moe"]
    assert moe["router"].shape == (2048, 64)
    assert moe["up"].shape == moe["gate"].shape == (8, 2048, 1536)
    assert moe["down"].shape == (8, 1536, 2048)
    conv = params["block_0"]["sconv"]
    assert conv["in_proj"].shape == (2048, 3, 2048)
    assert conv["conv_kernel"].shape == (3, 2048)
    assert conv["out_proj"].shape == (2048, 2048)
    assert params["block_1"]["mlp"]["gate"]["kernel"].shape == (2048, 11776)
    attn = params["block_2"]["attn"]
    assert attn["q"]["kernel"].shape == (2048, 32, 64)
    assert attn["k"]["kernel"].shape == (2048, 8, 64)
    assert attn["o"]["kernel"].shape == (32, 64, 2048)
    assert attn["q_norm"]["scale"].shape == (64,)
    assert job.probe.facts["pattern"] == lfm2_moe.PROBE_PATTERN
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_24b_a2b():
    """The issue's count of the uncut model: 23.84 B parameters, 2.3 B of
    them active a token."""
    conv, attention, dense = 16_783_360, 10_485_888, 72_351_744
    expert, router = 9_437_184, 131_072
    whole = (30 * conv + 10 * attention + 2 * dense
             + 38 * (64 * expert + router) + 65536 * 2048)
    assert whole / 1e9 == pytest.approx(23.84, abs=0.01)
    active = (30 * conv + 10 * attention + 2 * dense
              + 38 * (4 * expert + router) + 65536 * 2048)
    assert active / 1e9 == pytest.approx(2.3, abs=0.05)
    source = ["conv", "conv", "full_attention"] + [
        "conv", "conv", "conv", "full_attention"] * 9 + ["conv"]
    assert len(source) == 40 and source.count("conv") == 30
    assert lfm2_moe.layer_pattern(source, 2).count("-") == 2
    assert lfm2_moe.layer_pattern(source[1:6], 1) == "C-*ECECECE"


def test_configuration_keeps_the_sources_values():
    """Every number of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: a whole period and four
    layers after the leading dense one, 8 routed experts, an eighth of the
    vocabulary."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-24b-a2b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    published = config["published"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
    # the source's values, from the catalog's entry
    source = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
              "intermediate_size": 11776, "max_position_embeddings": 128000,
              "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
              "norm_eps": 1e-05, "norm_topk_prob": True,
              "num_attention_heads": 32, "num_experts_per_tok": 4,
              "num_key_value_heads": 8,
              "rope_parameters": {"rope_theta": 1000000,
                                  "rope_type": "default"},
              "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in source.items():
        assert config[key] == value, key
    assert {k: published[k] for k in ("num_hidden_layers", "num_dense_layers",
                                      "num_experts", "vocab_size")} == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536}
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert config["num_hidden_layers"] - config["num_dense_layers"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"]


@pytest.mark.parametrize("key, value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("tie_word_embeddings", False), ("model_type", "lfm2"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("layer_types", ["conv"])])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        lfm2_moe.build({**_config(), key: value}, CELL)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP is a mixer (its first product the in-projection, its second
    the gates and the convolution), block 1's is under the dense MLP's
    scope."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/", "/block_0/sconv/sconv_in_proj/"),
             ("/block_0/mlp/down/", "/block_0/sconv/sconv_gate_conv/"),
             ("/block_1/mlp/", "/block_1/mlp/dense_mlp/"))

    def rewrite(name):
        for old, new in swaps:
            name = name.replace(old, new)
        return name

    monkeypatch.setattr(regions, "name_stacks", lambda p: {
        k: rewrite(v) for k, v in names.items()})
    trace = xplane.load(str(path))
    by_scope = {old: sum(regions.region_ms(trace, names, scope=old)[r]
                         for r in ("forward", "recompute", "backward"))
                for old, _ in swaps}
    return trace, by_scope


def test_the_three_readers_read_their_scopes_or_nothing(renamed, monkeypatch):
    trace, by_scope = renamed
    read = lambda name: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, {})
    assert all(ms > 0 for ms in by_scope.values())
    assert read("sconv_gate_conv_ms") == pytest.approx(
        by_scope["/block_0/mlp/down/"])
    assert read("sconv_ms") == pytest.approx(
        by_scope["/block_0/mlp/up/"] + by_scope["/block_0/mlp/down/"])
    assert read("dense_mlp_ms") == pytest.approx(by_scope["/block_1/mlp/"])
    # the parent's program has none of the scopes: left out, not 0, and
    # nothing raised; so too without a device plane
    monkeypatch.undo()
    for name in READERS:
        module = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert module.read(None, {}) is None
    monkeypatch.setattr(regions, "name_stacks", lambda p: {"op": "jit(f)/x"})
    monkeypatch.setattr(regions, "trace_file", lambda *a: "somewhere")
    for name in READERS:
        assert importlib.import_module(
            f"chipbench.layer_metrics.{name}").read(trace, {}) is None


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "lfm2moe-s8192", "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is False           # a rehearsal never counts
    assert result["failed"] == 0 and result["attempted"] > 2
    assert not [l for l in lines if "FAILED" in l], lines
    for check in ("step_loss_vs_reference", "grad_vs_reference_given_experts",
                  "router_is_float32", "experts_agree_with_reference",
                  "disagreements_are_near_ties",
                  "sconv_mixer_vs_position_by_position"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    assert any("rows on the experts held" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {*READERS, "moe_ms", "lm_head_ms"}
