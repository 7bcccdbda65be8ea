"""The ``qwen3_next`` family: its FLOP and parameter counts against a hand
count and the package's tree; its configuration against the catalog's
rules; ``facts`` carrying what the readers read; its two readers on a
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
from chipbench.families import qwen3_next
from chipbench.layer_metrics import moe_experts_roofline
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 8192, "per_chip_batch": 2}


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/qwen3-next-80b.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand; three Gated DeltaNet, one attention, four expert layers and the
    head are the cell's 1.38 GFLOP a token."""
    gdn = (2048 * (2 * 2048 + 2 * 4096 + 2 * 32) + 4 * (2 * 2048 + 4096)
           + 3 * 32 * 128 * 128 + 4096 * 2048)
    assert gdn == 25_296_896 + 32_768 + 1_572_864 + 8_388_608 == 35_291_136
    attention = (2 * 2048 * 16 * 256 + 2 * 2048 * 2 * 256 + 16 * 256 * 2048
                 + 16 * 256 * 8192)
    assert attention == 16_777_216 + 2_097_152 + 8_388_608 + 33_554_432
    experts = (2048 * 512 + 3 * 2048 * 512 + 2048
               + 10 * 32 / 512 * 3 * 2048 * 512)
    assert experts == 1_048_576 + 3_145_728 + 2_048 + 1_966_080
    head = 18992 * 2048
    job = qwen3_next.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"G": 3 * gdn, "*": attention, "E": 4 * experts,
                    "head": head}
    total = 3 * gdn + attention + 4 * experts + head
    assert job.flops_per_item == 6.0 * total == 1_381_416_960.0
    assert job.items_per_step_per_chip == 16384
    # the shares of the arithmetic the issue reckoned with
    assert 3 * gdn / total == pytest.approx(0.46, abs=0.005)
    assert attention / total == pytest.approx(0.264, abs=0.005)
    assert head / total == pytest.approx(0.169, abs=0.005)
    assert 4 * experts / total == pytest.approx(0.107, abs=0.005)


def test_facts_carry_what_the_readers_read():
    job = qwen3_next.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 4, "rows": 10240, "experts": 32, "d_model": 2048,
        "d_expert": 512, "itemsize": 2, "row_bound": 163840,
        "routed_over": 512}
    assert job.facts["gdn"] == {"layers": 3, "value_heads": 32, "chunk": 128}
    assert job.facts["remat"] is True and job.facts["pattern"] == "GEGEGE*E"
    least, bound = moe_experts_roofline.least_ms(
        job.facts, flops.peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(7.0, abs=0.3)


def test_parameters_of_the_cut_are_the_trees():
    """625.7 M parameters, 9.32 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order."""
    job = qwen3_next.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 625_667_136
    assert 16 * size(params) / 2 ** 30 == pytest.approx(9.32, abs=0.01)
    assert extra == {}
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(8)]
    assert kinds == ["gdn", "moe", "gdn", "moe", "gdn", "moe", "attn", "moe"]
    assert size(params["block_0"]["gdn"]) == 33_718_464
    assert size(params["block_6"]["attn"]) == 27_263_488
    assert size(params["block_1"]["moe"]) == 104_859_648
    assert size((params["embedding"], params["lm_head"])) == 77_791_232
    assert size([params[f"block_{i}"]["norm"] for i in range(8)]
                + [params["ln_f"]]) == 18_432
    moe = params["block_1"]["moe"]
    assert moe["router"].shape == (2048, 512)
    assert moe["up"].shape == (32, 2048, 512)
    assert moe["down"].shape == (32, 512, 2048)
    assert moe["shared_expert_gate"].shape == (2048, 1)
    gdn = params["block_0"]["gdn"]
    assert gdn["in_proj_qkvz"].shape == (2048, 12288)
    assert gdn["in_proj_ba"].shape == (2048, 64)
    assert gdn["conv_kernel"].shape == (4, 8192)
    attn = params["block_6"]["attn"]
    assert attn["q"]["kernel"].shape == (2048, 16, 512)
    assert attn["k"]["kernel"].shape == (2048, 2, 256)
    assert attn["o"]["kernel"].shape == (16, 256, 2048)
    assert attn["q_norm"]["scale"].shape == (256,)
    assert job.probe.facts["pattern"] == qwen3_next.PROBE_PATTERN
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_initialisation_is_the_assumed():
    """normal(0.02) for the attention's projections too (the package's
    layers draw them lecun-normal), each from its own key; the norms'
    weights 0, the mixer's own norm's scale 1."""
    config = {**_config(), **qwen3_next.REHEARSAL["config"]}
    job = qwen3_next.build(config, qwen3_next.REHEARSAL["traffic"])
    params, _ = jax.jit(job.init)(jax.random.key(0))
    attn = params["block_2"]["attn"]
    kernels = [attn[name]["kernel"] for name in "qkvo"]
    for kernel in kernels:
        assert float(kernel.std()) == pytest.approx(0.02, rel=0.15)
    assert float(abs(kernels[1] - kernels[2]).max()) > 0.01
    assert float(abs(params["block_0"]["norm"]["scale"]).max()) == 0.0
    assert float(abs(attn["q_norm"]["scale"]).max()) == 0.0
    assert float(params["block_0"]["gdn"]["norm_scale"].min()) == 1.0


def test_configuration_keeps_the_sources_values():
    """Every number of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: a whole period of four
    layers, 8 routed experts at least, an eighth of the vocabulary."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "qwen3-next-80b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    published = config["published"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
    # the source's values, from the catalog's entry
    source = {"decoder_sparse_step": 1, "full_attention_interval": 4,
              "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
              "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
              "linear_key_head_dim": 128, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_value_head_dim": 128,
              "max_position_embeddings": 262144, "mlp_only_layers": [],
              "model_type": "qwen3_next", "moe_intermediate_size": 512,
              "norm_topk_prob": True, "num_attention_heads": 16,
              "num_experts_per_tok": 10, "num_key_value_heads": 2,
              "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
              "rope_scaling": None, "rope_theta": 10000000,
              "shared_expert_intermediate_size": 512,
              "tie_word_embeddings": False, "use_sliding_window": False}
    for key, value in source.items():
        assert config[key] == value, key
    assert published == {"num_hidden_layers": 48, "num_experts": 512,
                         "vocab_size": 151936,
                         "multi_token_prediction_modules": 1}
    assert config["num_hidden_layers"] == config["full_attention_interval"]
    assert qwen3_next.layer_pattern(48, 4) == "GEGEGE*E" * 12
    assert qwen3_next.layer_pattern(4, 4) == "GEGEGE*E"
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key]


@pytest.mark.parametrize("key, value", [
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("num_nextn_predict_layers", 1)])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        qwen3_next.build({**_config(), key: value}, CELL)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP is the rule, block 1's an in-projection."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/", "/block_0/gdn/gdn_rule/"),
             ("/block_1/mlp/", "/block_1/gdn/gdn_in_proj/"))

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


def test_the_two_readers_read_their_scopes_or_nothing(renamed, monkeypatch):
    trace, by_scope = renamed
    read = lambda name: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, {})
    assert all(ms > 0 for ms in by_scope.values())
    assert read("gdn_rule_ms") == pytest.approx(by_scope["/block_0/mlp/"])
    assert read("gdn_ms") == pytest.approx(
        by_scope["/block_0/mlp/"] + by_scope["/block_1/mlp/"])
    # the parent's program has none of the scopes: left out, not 0, and
    # nothing raised; so too without a device plane
    monkeypatch.undo()
    for name in ("gdn_ms", "gdn_rule_ms"):
        module = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert module.read(None, {}) is None
    monkeypatch.setattr(regions, "name_stacks", lambda p: {"op": "jit(f)/x"})
    monkeypatch.setattr(regions, "trace_file", lambda *a: "somewhere")
    for name in ("gdn_ms", "gdn_rule_ms"):
        assert importlib.import_module(
            f"chipbench.layer_metrics.{name}").read(trace, {}) is None


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "qwen3next-s8192", "--seed", "2147483999", "--seconds", "1",
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
                  "gdn_mixer_vs_position_by_position"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    assert any("rows on the experts held" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert set(result["metrics"]) <= {"compile_s", "hbm_reserved"}
