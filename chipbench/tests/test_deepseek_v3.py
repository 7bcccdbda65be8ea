"""The ``deepseek_v3`` family: its FLOP and parameter counts against a
hand count and the package's tree; the products over positions a step
requires of its latent attention; its configuration against the catalog's
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
from chipbench.families import deepseek_v3
from chipbench.layer_metrics import mla_core_roofline, moe_experts_roofline
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 8192, "per_chip_batch": 2}
READERS = ("mla_ms", "mla_core_ms", "mla_core_roofline")


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/kanana-2-30b-a3b.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand; six latent attentions, the dense MLP, five expert layers and the
    untied head are the cell's 3.279 GFLOP a token, latent attention 75%
    of it."""
    projections = (2048 * 32 * 192 + 2048 * (512 + 64) + 512 * 32 * 256
                   + 32 * 128 * 2048)
    assert projections == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608
    over_positions = 32 * 320 * 8193 / 2
    assert over_positions == 41_948_160
    dense = 3 * 2048 * 6144
    assert dense == 37_748_736
    experts = 2048 * 128 + 3 * 2048 * 1536 + 6 * 16 / 128 * 3 * 2048 * 768
    assert experts == 262_144 + 9_437_184 + 3_538_944
    head = 16032 * 2048
    job = deepseek_v3.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"L": 6 * (projections + over_positions), "-": dense,
                    "E": 5 * experts, "head": head}
    total = 6 * (projections + over_positions) + dense + 5 * experts + head
    assert total == 546_535_424
    assert job.flops_per_item == 6.0 * total == 3_279_212_544.0
    assert job.items_per_step_per_chip == 16384
    # a step is 53.7 TFLOP: 0.78 s at 35% of 197 TFLOP/s
    step = job.flops_per_item * 16384
    assert step / 1e12 == pytest.approx(53.7, abs=0.05)
    assert step / (0.35 * 197e12) == pytest.approx(0.78, abs=0.005)
    assert 6 * (projections + over_positions) / total == pytest.approx(
        0.75, abs=0.005)
    assert 6 * over_positions / total == pytest.approx(0.46, abs=0.005)
    assert dense / total == pytest.approx(0.069, abs=0.005)
    assert 5 * experts / total == pytest.approx(0.121, abs=0.005)
    assert head / total == pytest.approx(0.060, abs=0.005)


def test_products_over_positions_a_step_requires():
    """1,472 multiply-adds a visible pair under remat (the forward twice
    at 192 + 128, the backward's five products 3 x 192 + 2 x 128), the
    pairs counted exactly: 32.1 ms a layer at 197 TFLOP/s, 192.6 ms a step,
    compute-bound; a padded width or whole tiles would count more."""
    pairs = 2 * 32 * 8192 * 8193 // 2
    assert pairs == 2_147_745_792
    assert 2 * 320 + 832 == 1472
    macs = deepseek_v3.mla_core_macs_per_step(
        layers=6, batch=2, heads=32, seq_len=8192, qk_dim=192, v_dim=128,
        remat=True)
    assert macs == 6 * pairs * 1472
    assert deepseek_v3.mla_core_macs_per_step(
        layers=1, batch=2, heads=32, seq_len=8192, qk_dim=192, v_dim=128,
        remat=False) == pairs * (320 + 832)
    peak = flops.peaks("TPU v5 lite")
    assert 2 * macs / 6 / peak["bf16_flops_per_s"] == pytest.approx(
        32.1e-3, abs=5e-5)
    job = deepseek_v3.build(_config(), CELL)
    shape = job.facts["mla"]
    assert shape == {
        "layers": 6, "batch": 2, "heads": 32, "seq_len": 8192, "qk_dim": 192,
        "v_dim": 128, "core_macs_per_step": macs,
        "core_bytes_per_step": deepseek_v3.mla_core_bytes_per_step(
            layers=6, batch=2, heads=32, seq_len=8192, qk_dim=192,
            v_dim=128, remat=True)}
    # bytes a position of a sequence and head: forward 2 (192 + 192 + 128 +
    # 128) + 4 twice, backward 2 (4 x 192 + 3 x 128) + 8
    assert shape["core_bytes_per_step"] == 6 * 2 * 32 * 8192 * (
        2 * 1284 + 2312)
    seconds, bound = flops.roofline_seconds(
        2.0 * macs, shape["core_bytes_per_step"], peak)
    assert bound == "compute" and 1e3 * seconds == pytest.approx(
        192.6, abs=0.05)
    # the same count padded to 256 | 128 or to 256 | 256: the zeros
    padded = lambda qk, v: deepseek_v3.mla_core_macs_per_step(
        layers=6, batch=2, heads=32, seq_len=8192, qk_dim=qk, v_dim=v,
        remat=True)
    assert padded(256, 128) / macs == pytest.approx(1.217, abs=0.001)
    assert padded(256, 256) / macs == pytest.approx(1.565, abs=0.001)


def test_facts_carry_what_the_readers_read():
    job = deepseek_v3.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 5, "rows": 12288, "experts": 16, "d_model": 2048,
        "d_expert": 768, "itemsize": 2, "row_bound": 98304,
        "routed_over": 128}
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "L-LELELELELE"
    least, bound = moe_experts_roofline.least_ms(
        job.facts, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(11.77, abs=0.05)


def test_parameters_of_the_cut_are_the_trees():
    """687.5 M parameters, 10.24 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order, as the issue counted it."""
    job = deepseek_v3.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 687_502_336
    assert 16 * size(params) / 1e9 == pytest.approx(11.00, abs=0.005)
    assert 16 * size(params) / 2 ** 30 == pytest.approx(10.24, abs=0.005)
    assert set(extra["buffers"]) == {"block_3", "block_5", "block_7",
                                     "block_9", "block_11"}
    assert extra["buffers"]["block_3"]["moe"]["choice_bias"].shape == (128,)
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(12)]
    assert kinds == ["mla", "mlp"] + ["mla", "moe"] * 5
    mla = params["block_0"]["mla"]
    assert {k: v.shape for k, v in mla.items()} == {
        "q_proj": (2048, 32, 192), "kv_down": (2048, 576),
        "kv_norm": (512,), "kv_up": (512, 32, 256),
        "o_proj": (32, 128, 2048)}
    assert size(mla) == 26_345_984
    assert size(params["block_1"]["mlp"]) == 37_748_736
    assert size((mla, params["block_1"]["mlp"])) + 2 * 2048 == 64_098_816 \
        == 64_094_720 + 2 * 2048
    moe = params["block_3"]["moe"]
    assert moe["router"].shape == (2048, 128)
    assert moe["up"].shape == moe["gate"].shape == (16, 2048, 768)
    assert moe["down"].shape == (16, 768, 2048)
    assert moe["shared_up"].shape == moe["shared_gate"].shape == (2048, 1536)
    assert "shared_expert_gate" not in moe
    assert size(moe) == 262_144 + 9_437_184 + 75_497_472
    assert size((params["block_2"]["mla"], moe)) == 111_542_784
    assert params["lm_head"].shape == params["embedding"].shape == (16032,
                                                                    2048)
    assert size((params["embedding"], params["lm_head"])) == 65_667_072
    assert size([params[f"block_{i}"]["norm"] for i in range(12)]
                + [params["ln_f"]]) == 26_624
    assert job.probe.facts["pattern"] == deepseek_v3.PROBE_PATTERN
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_30b_a3b():
    """The issue's count of the uncut model: 30.67 B parameters, 3.09 B of
    them active a token outside the embedding and the head."""
    attention, dense = 26_345_984, 37_748_736
    expert, router, shared = 4_718_592, 262_144, 9_437_184
    whole = (48 * attention + dense + 47 * (128 * expert + router + shared)
             + 2 * 128256 * 2048 + 97 * 2048)
    assert whole / 1e9 == pytest.approx(30.67, abs=0.01)
    active = 48 * attention + dense + 47 * (6 * expert + router + shared)
    assert active / 1e9 == pytest.approx(3.09, abs=0.01)
    assert deepseek_v3.layer_pattern(48, 1).count("E") == 47
    assert deepseek_v3.layer_pattern(6, 1) == "L-LELELELELE"


def test_configuration_keeps_the_sources_values():
    """Every number of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: four layers after the
    leading dense one, 8 routed experts, an eighth of the vocabulary."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "kanana-2-30b-a3b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    published = config["published"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
    # the source's values, from the catalog's entry
    source = {"attention_bias": False, "first_k_dense_replace": 1,
              "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
              "intermediate_size": 6144, "kv_lora_rank": 512,
              "max_position_embeddings": 32768, "model_type": "deepseek_v3",
              "moe_intermediate_size": 768, "moe_layer_freq": 1,
              "n_group": 1, "n_shared_experts": 2, "norm_topk_prob": True,
              "num_attention_heads": 32, "num_experts_per_tok": 6,
              "num_key_value_heads": 32, "q_lora_rank": None,
              "qk_head_dim": 192, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
              "rope_interleave": True, "rope_scaling": None,
              "rope_theta": 1000000, "routed_scaling_factor": 2.448,
              "scoring_func": "sigmoid", "tie_word_embeddings": False,
              "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in source.items():
        assert key in config and config[key] == value, key
    assert published == {"num_hidden_layers": 48, "n_routed_experts": 128,
                         "vocab_size": 128256}
    assert (config["num_hidden_layers"]
            - config["first_k_dense_replace"]) >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"]


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("scoring_func", "softmax"), ("rope_interleave", False),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("tie_word_embeddings", True), ("model_type", "deepseek_v2"),
    ("num_key_value_heads", 8), ("qk_head_dim", 128)])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        deepseek_v3.build({**_config(), key: value}, CELL)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's attention is a latent attention (its q product the q projection,
    its o product under ``mla_core``), block 1's MLP is under the dense
    MLP's scope."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/", "/block_0/mla/mla_q_proj/"),
             ("/block_0/mlp/down/", "/block_0/mla/mla_core/"))

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
    assert read("mla_core_ms") == pytest.approx(
        by_scope["/block_0/mlp/down/"])
    assert read("mla_ms") == pytest.approx(
        by_scope["/block_0/mlp/up/"] + by_scope["/block_0/mlp/down/"])
    # the recording holds no Pallas call: no kernel under the scope, so the
    # roofline is left out (and would be with the shapes given, too)
    job = deepseek_v3.build(_config(), CELL)
    run = {"facts": job.facts, "peak": flops.peaks("TPU v5 lite")}
    assert mla_core_roofline.kernels_ms(trace) == 0
    assert read("mla_core_roofline", run) is None
    # a kernel event under the scope is counted, one outside it is not
    device = trace.devices[0]
    lo, hi, steps = trace.window(device)
    call = ('%attn.1 = bf16[2] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    other = call.replace("attn.1", "gmm.2")
    span = (hi - lo) / 4
    device.ops.extend([xplane.Op(call, lo, lo + span, "kernel"),
                       xplane.Op(other, lo, lo + span, "kernel")])
    named = regions.name_stacks("x")
    monkeypatch.setattr(regions, "name_stacks", lambda p: {
        **named, "attn.1": "jit(step)/jvp(deepseek_v3)/block_0/mla/mla_core/"
                           "hvt_flash_fwd",
        "gmm.2": "jit(step)/jvp(deepseek_v3)/block_3/moe/moe_experts/gmm"})
    assert mla_core_roofline.kernels_ms(trace) == pytest.approx(
        span / steps / 1e6)
    share = read("mla_core_roofline", run)
    assert share == pytest.approx(100 * 192.5776 / (span / steps / 1e6),
                                  rel=1e-4)
    assert read("mla_core_roofline", {"facts": {}, "peak": run["peak"]}) \
        is None
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
         "kanana2-s8192", "--seed", "2147483999", "--seconds", "1",
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
                  "mla_mixer_vs_reference_by_query_blocks"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    assert any("rows on the experts held" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {*READERS, "moe_ms", "lm_head_ms"}
