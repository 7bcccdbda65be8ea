"""The ``kimi_linear`` family: its FLOP and parameter counts against a
hand count and the package's tree; the delta rule's work as the recurrence
requires it; its configuration against the catalog's rules; ``facts``
carrying what the readers read; its three readers on a recorded trace
whose names are rewritten; and the cell's rehearsal."""

import gzip
import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import flops, regions, xplane
from chipbench.families import kimi_linear
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 8192, "per_chip_batch": 2}
READERS = ("kda_ms", "kda_rule_ms", "kda_rule_roofline")


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand; four delta-rule layers, one latent attention, the dense MLP, four
    expert layers and the untied head are the cell's 2.304 GFLOP a token,
    the new mixer 43% of it."""
    products = (3 * 2304 * 4096 + 2304 * 32 + 2 * (2304 * 128 + 128 * 4096)
                + 4096 * 2304)
    assert products == 39_460_864
    taps, rule = 4 * 3 * 4096, 3 * 32 * 128 * 128
    assert (taps, rule) == (49_152, 1_572_864)
    kda = products + taps + rule
    assert kda == 41_082_880
    projections = (2304 * 32 * 192 + 2304 * (512 + 64) + 512 * 32 * 256
                   + 32 * 128 * 2304)
    assert projections == 29_114_368
    over_positions = 32 * 320 * 8193 / 2
    dense = 3 * 2304 * 9216
    assert dense == 63_700_992
    experts = 2304 * 256 + 3 * 2304 * 1024 + 8 * 8 / 256 * 3 * 2304 * 1024
    assert experts == 589_824 + 7_077_888 + 1_769_472
    head = 20480 * 2304
    job = kimi_linear.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"K": 4 * kda, "L": projections + over_positions,
                    "-": dense, "E": 4 * experts, "head": head}
    total = sum(macs.values())
    assert total / 1e6 == pytest.approx(384.0, abs=0.05)
    assert job.flops_per_item == 6.0 * total
    assert job.flops_per_item / 1e9 == pytest.approx(2.304, abs=0.001)
    assert job.items_per_step_per_chip == 16384
    assert job.flops_per_item * 16384 / 1e12 == pytest.approx(37.8, abs=0.05)
    assert 4 * kda / total == pytest.approx(0.43, abs=0.005)
    assert (projections + over_positions) / total == pytest.approx(
        0.185, abs=0.005)
    assert dense / total == pytest.approx(0.166, abs=0.005)
    assert head / total == pytest.approx(0.123, abs=0.005)
    assert 4 * experts / total == pytest.approx(0.098, abs=0.005)


def test_the_rules_work_as_the_recurrence_requires_it():
    """``3 H d_h^2`` multiply-adds a position forward (the state's read by
    the key, its write, its read by the query), again under remat, twice
    backward: 4 x 3 = 12 x 1.57 M a token and layer, whatever the chunk;
    the bytes are the operands' and their gradients' once a pass, which
    bound it: 15.1 ms a step at 819 GB/s against 12.6 ms of products."""
    macs = kimi_linear.kda_rule_macs_per_step(
        layers=4, batch=2, heads=32, seq_len=8192, head_dim=128, remat=True)
    assert macs == 4 * 16384 * 1_572_864 * 4
    assert kimi_linear.kda_rule_macs_per_step(
        layers=1, batch=2, heads=32, seq_len=8192, head_dim=128,
        remat=False) == 16384 * 1_572_864 * 3
    moved = kimi_linear.kda_rule_bytes_per_step(
        layers=4, batch=2, heads=32, seq_len=8192, head_dim=128, remat=True)
    forward = 2 * 4 * 4096 + 4 * (4096 + 32)
    backward = forward + 2 * 3 * 4096 + 4 * (4096 + 32)
    assert moved == 4 * 16384 * (2 * forward + backward)
    peak = flops.peaks("TPU v5 lite")
    seconds, bound = flops.roofline_seconds(2.0 * macs, moved, peak)
    assert bound == "memory" and 1e3 * seconds == pytest.approx(15.1, abs=0.05)
    assert 1e3 * 2.0 * macs / peak["bf16_flops_per_s"] == pytest.approx(
        4.19, abs=0.01)
    job = kimi_linear.build(_config(), CELL)
    assert job.facts["kda"] == {
        "layers": 4, "batch": 2, "heads": 32, "seq_len": 8192,
        "head_dim": 128, "chunk": 32, "rule_macs_per_step": macs,
        "rule_bytes_per_step": moved}


def test_facts_carry_what_the_readers_read():
    job = kimi_linear.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 4, "rows": 4096, "experts": 8, "d_model": 2304,
        "d_expert": 1024, "itemsize": 2, "row_bound": 131072,
        "routed_over": 256}
    assert job.facts["mla"]["layers"] == 1
    assert job.facts["mla"]["qk_dim"] == 192
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "K-KEKELEKE"


def test_parameters_of_the_cut_are_the_trees():
    """602.4 M parameters, 8.98 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order, as the issue counted it."""
    job = kimi_linear.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 602_433_408
    assert 16 * size(params) / 1e9 == pytest.approx(9.64, abs=0.005)
    assert 16 * size(params) / 2 ** 30 == pytest.approx(8.98, abs=0.005)
    assert set(extra["buffers"]) == {"block_3", "block_5", "block_7",
                                     "block_9"}
    assert extra["buffers"]["block_3"]["moe"]["choice_bias"].shape == (256,)
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(10)]
    assert kinds == ["kda", "mlp", "kda", "moe", "kda", "moe", "mla", "moe",
                     "kda", "moe"]
    kda = params["block_0"]["kda"]
    assert {k: v.shape for k, v in kda.items()} == {
        "in_proj_qkv": (2304, 12288), "conv_kernel": (4, 12288),
        "in_proj_beta": (2304, 32), "decay_down": (2304, 128),
        "decay_up": (128, 4096), "dt_bias": (4096,), "A_log": (32,),
        "gate_down": (2304, 128), "gate_up": (128, 4096),
        "norm_scale": (128,), "out_proj": (4096, 2304)}
    assert size(kda) == 39_514_272
    assert size(params["block_1"]["mlp"]) == 63_700_992
    assert size((kda, params["block_1"]["mlp"])) + 2 * 2304 == 103_219_872
    mla = params["block_6"]["mla"]
    assert {k: v.shape for k, v in mla.items()} == {
        "q_proj": (2304, 32, 192), "kv_down": (2304, 576),
        "kv_norm": (512,), "kv_up": (512, 32, 256),
        "o_proj": (32, 128, 2304)}
    assert size(mla) == 29_114_880
    moe = params["block_3"]["moe"]
    assert moe["router"].shape == (2304, 256)
    assert moe["up"].shape == moe["gate"].shape == (8, 2304, 1024)
    assert moe["shared_up"].shape == moe["shared_gate"].shape == (2304, 1024)
    assert "shared_expert_gate" not in moe
    assert size(moe) == 589_824 + 7_077_888 + 56_623_104
    assert size((kda, moe)) + 2 * 2304 == 103_809_696
    assert size((mla, moe)) + 2 * 2304 == 93_410_304
    assert params["lm_head"].shape == params["embedding"].shape == (20480,
                                                                    2304)
    assert size((params["embedding"], params["lm_head"])) == 94_371_840
    assert job.probe.facts["pattern"] == kimi_linear.PROBE_PATTERN
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_48b_a3b():
    """The issue's count of the uncut model: 49.1 B parameters, 3.5 B of
    them active a token with the embedding and the head."""
    kda, mla, dense = 39_514_272, 29_114_880, 63_700_992
    expert, router, shared = 7_077_888, 589_824, 7_077_888
    table = 2 * 163840 * 2304
    whole = (20 * kda + 7 * mla + dense
             + 26 * (256 * expert + router + shared) + table)
    assert whole / 1e9 == pytest.approx(49.1, abs=0.1)
    active = (20 * kda + 7 * mla + dense
              + 26 * (8 * expert + router + shared) + table)
    assert active / 1e9 == pytest.approx(3.5, abs=0.1)
    linear = _config()["linear_attn_config"]
    whole_pattern = kimi_linear.layer_pattern(
        27, 1, linear["kda_layers"], linear["full_attn_layers"])
    assert (whole_pattern.count("K"), whole_pattern.count("L"),
            whole_pattern.count("E")) == (20, 7, 26)
    assert kimi_linear.layer_pattern(
        5, 1, linear["kda_layers"], linear["full_attn_layers"]) \
        == "K-KEKELEKE"


def test_configuration_keeps_the_sources_values():
    """Every number of the catalog's entry under its own key but those in
    ``reduced``, the nested group whole; no width among the cuts; the
    floors: four layers after the leading dense one, 8 routed experts, an
    eighth of the vocabulary."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "kimi-linear-48b-a3b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    published = config["published"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
    source = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    for key, value in source.items():
        assert key in config and config[key] == value, key
    assert published == {"num_hidden_layers": 27, "num_experts": 256,
                         "vocab_size": 163840}
    assert (config["num_hidden_layers"]
            - config["first_k_dense_replace"]) >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key]
    assert "32 chips share each layer" in config["deployment"]


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("num_expert_group", 8), ("topk_group", 4),
    ("moe_router_activation_func", "softmax"), ("mla_use_nope", False),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("tie_word_embeddings", True), ("model_type", "deepseek_v3"),
    ("num_key_value_heads", 8), ("num_nextn_predict_layers", 1)])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        kimi_linear.build({**_config(), key: value}, CELL)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP is a Kimi Delta Attention mixer, its first product the
    in-projections and its second under ``kda_rule``."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/", "/block_0/kda/kda_in_proj/"),
             ("/block_0/mlp/down/", "/block_0/kda/kda_rule/"))

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
    read = lambda name, run={}: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, run)
    assert all(ms > 0 for ms in by_scope.values())
    assert read("kda_rule_ms") == pytest.approx(
        by_scope["/block_0/mlp/down/"])
    assert read("kda_ms") == pytest.approx(
        by_scope["/block_0/mlp/up/"] + by_scope["/block_0/mlp/down/"])
    job = kimi_linear.build(_config(), CELL)
    run = {"facts": job.facts, "peak": flops.peaks("TPU v5 lite")}
    share = read("kda_rule_roofline", run)
    assert share == pytest.approx(
        100 * 15.1179 / by_scope["/block_0/mlp/down/"], rel=1e-4)
    assert read("kda_rule_roofline", {"facts": {}, "peak": run["peak"]}) \
        is None
    # a loop's event under the scope spans its body's and is left out; a
    # kernel under it is counted as any operation is
    device = trace.devices[0]
    lo, hi, steps = trace.window(device)
    span = (hi - lo) / 4
    loop = "%while.7 = (f32[2]) while(%tuple.1), body=%b, condition=%c"
    call = ('%rule.1 = bf16[2] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    device.ops.extend([xplane.Op(loop, lo, lo + span, "other"),
                       xplane.Op(call, lo, lo + span, "kernel")])
    named = regions.name_stacks("x")
    under = "jit(step)/jvp(kimi_linear)/block_0/kda/kda_rule/"
    monkeypatch.setattr(regions, "name_stacks", lambda p: {
        **named, "while.7": under + "while", "rule.1": under + "a_kernel"})
    assert read("kda_rule_ms") == pytest.approx(
        by_scope["/block_0/mlp/down/"] + span / steps / 1e6)
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
            f"chipbench.layer_metrics.{name}").read(trace, run) is None


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "kimilinear-s8192", "--seed", "2147483999", "--seconds", "1",
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
                  "kda_mixer_vs_position_by_position",
                  "mla_mixer_vs_reference_by_query_blocks"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    assert any("rows on the experts held" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {*READERS, "moe_ms", "lm_head_ms"}
