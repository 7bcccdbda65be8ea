"""The ``afmoe`` family: its FLOP and parameter counts against a hand
count and the package's tree; the products over positions a step requires
of its windowed layers; its configuration against the catalog's entry;
``facts`` carrying what the readers read; its three readers on a recorded
trace whose names are rewritten; the reference's mask from positions and
its separate gate; and the cell's rehearsal."""

import gzip
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, regions, xplane
from chipbench.families import afmoe
from chipbench.layer_metrics import attn_window_roofline, moe_experts_roofline
from chipbench.reference import afmoe as reference
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 16384, "per_chip_batch": 1}
READERS = ("attn_window_core_ms", "attn_window_roofline", "post_norm_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/trinity-mini.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand: 631.7 M multiply-adds, 3.790 GFLOP a token, 62.1 TFLOP a step,
    the attention mixers 71% of it and the windowed layers' products over
    positions 15%; masked without skipping they would be 402.7 M."""
    projections = 2 * 8_388_608 + 2 * 1_048_576 + 8_388_608
    assert projections == 2048 * (3 * 32 + 2 * 4) * 128 == 27_262_976
    assert afmoe.band_pairs(16384, 2048) == 31_458_304 == (
        2048 * 2049 // 2 + (16384 - 2048) * 2048)
    windowed = 32 * 256 * 31_458_304 / 16384
    full = 32 * 256 * 16385 / 2
    assert windowed == 15_729_152 and full == 67_112_960
    dense = 3 * 2048 * 6144
    experts = 2048 * 128 + 3 * 2048 * 1024 + 8 * 8 / 128 * 3 * 2048 * 1024
    assert experts == 262_144 + 6_291_456 + 3_145_728
    head = 25024 * 2048
    job = afmoe.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"W": 6 * (projections + windowed),
                    "*": 2 * (projections + full), "-": 2 * dense,
                    "E": 6 * experts, "head": head}
    total = sum(macs.values())
    assert total == 631_647_232
    assert job.flops_per_item == 6.0 * total == 3_789_883_392.0
    assert job.items_per_step_per_chip == 16384
    assert job.flops_per_item * 16384 / 1e12 == pytest.approx(62.1, abs=0.05)
    assert (macs["W"] + macs["*"]) / total == pytest.approx(0.71, abs=0.005)
    assert 8 * projections / total == pytest.approx(0.345, abs=0.005)
    assert 2 * full / total == pytest.approx(0.21, abs=0.005)
    assert 6 * windowed / total == pytest.approx(0.15, abs=0.005)
    assert macs["-"] / total == pytest.approx(0.12, abs=0.005)
    assert macs["E"] / total == pytest.approx(0.09, abs=0.005)
    assert head / total == pytest.approx(0.08, abs=0.005)
    assert 6 * full == pytest.approx(402.7e6, rel=1e-3)
    assert afmoe.band_pairs(8192, 2048) / (8192 * 8193 / 2) == pytest.approx(
        0.437, abs=0.001)
    assert 31_458_304 / (16384 * 16385 / 2) == pytest.approx(0.234, abs=0.001)
    # a window no shorter than the sequence is the causal count
    assert afmoe.band_pairs(1024, 2048) == 1024 * 1025 // 2


def test_products_over_positions_the_windowed_layers_require():
    """1,152 multiply-adds a pair of the band under remat (the forward
    twice at 128 + 128, the backward's five products), the pairs counted
    exactly: 11.77 ms a layer at 197 TFLOP/s, 70.6 ms a step, compute-bound;
    every causal pair would count 4.27 times as much."""
    pairs = 32 * 31_458_304
    macs = afmoe.attn_window_macs_per_step(
        layers=6, batch=1, heads=32, seq_len=16384, head_dim=128,
        window=2048, remat=True)
    assert macs == 6 * pairs * 128 * 9 == 6_958_073_511_936
    assert afmoe.attn_window_macs_per_step(
        layers=1, batch=2, heads=32, seq_len=16384, head_dim=128,
        window=2048, remat=False) == 2 * pairs * 128 * 7
    nbytes = afmoe.attn_window_bytes_per_step(
        layers=6, batch=1, heads=32, kv_heads=4, seq_len=16384, head_dim=128,
        remat=True)
    # bytes a position: forward 2 x 128 (2 x 32 + 2 x 4) + 4 x 32 twice,
    # backward 2 x 128 (4 x 32 + 4 x 4) + 8 x 32
    assert nbytes == 6 * 16384 * (2 * 18_560 + 37_120) == 7_298_088_960
    job = afmoe.build(_config(), CELL)
    assert job.facts["attn_window"] == {
        "layers": 6, "batch": 1, "heads": 32, "kv_heads": 4,
        "seq_len": 16384, "head_dim": 128, "window": 2048,
        "band_pairs": 31_458_304, "macs_per_step": macs,
        "bytes_per_step": nbytes}
    seconds, bound = flops.roofline_seconds(
        2.0 * macs, nbytes, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * seconds == pytest.approx(
        70.64, abs=0.01)
    causal = afmoe.attn_window_macs_per_step(
        layers=6, batch=1, heads=32, seq_len=16384, head_dim=128,
        window=16384, remat=True)
    assert causal / macs == pytest.approx(4.27, abs=0.005)


def test_facts_carry_what_the_readers_read():
    job = afmoe.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 6, "rows": 8192, "experts": 8, "d_model": 2048,
        "d_expert": 1024, "itemsize": 2, "row_bound": 131072,
        "routed_over": 128}
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "W-W-WE*EWEWEWE*E"
    least, bound = moe_experts_roofline.least_ms(
        job.facts, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(12.56, abs=0.05)


def test_parameters_of_the_cut_are_the_trees():
    """737,480,704 parameters, 10.99 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order, as the issue counted it."""
    job = afmoe.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 737_480_704
    assert 16 * size(params) / 1e9 == pytest.approx(11.80, abs=0.005)
    assert 16 * size(params) / 2 ** 30 == pytest.approx(10.99, abs=0.005)
    assert set(extra["buffers"]) == {f"block_{i}" for i in range(5, 16, 2)}
    assert extra["buffers"]["block_5"]["moe"]["choice_bias"].shape == (128,)
    kinds = ["".join(sorted(set(params[f"block_{i}"])
                            - {"norm", "post_norm"})) for i in range(16)]
    assert kinds == ["attn", "mlp"] * 2 + ["attn", "moe"] * 6
    attn = params["block_0"]["attn"]
    assert jax.tree.map(lambda a: a.shape, attn) == {
        "q": {"kernel": (2048, 32, 256)}, "k": {"kernel": (2048, 4, 128)},
        "v": {"kernel": (2048, 4, 128)}, "o": {"kernel": (32, 128, 2048)},
        "q_norm": {"scale": (128,)}, "k_norm": {"scale": (128,)}}
    assert size(attn) == 27_263_232 == size(params["block_6"]["attn"])
    assert size(params["block_1"]["mlp"]) == 37_748_736
    moe = params["block_5"]["moe"]
    assert moe["router"].shape == (2048, 128)
    assert moe["up"].shape == moe["gate"].shape == (8, 2048, 1024)
    assert moe["down"].shape == (8, 1024, 2048)
    assert moe["shared_up"].shape == moe["shared_gate"].shape == (2048, 1024)
    assert "shared_expert_gate" not in moe
    assert size(moe) == 262_144 + 50_331_648 + 6_291_456 == 56_885_248
    assert params["lm_head"].shape == params["embedding"].shape == (25024,
                                                                    2048)
    assert size((params["embedding"], params["lm_head"])) == 102_498_304
    norms = [params[f"block_{i}"][name] for i in range(16)
             for name in ("norm", "post_norm")]
    assert size(norms) == 8 * 8_192 and size(params["ln_f"]) == 2_048
    assert (8 * 27_271_424 + 2 * 37_748_736 + 6 * 56_885_248 + 102_498_304
            + 2_048) == 737_480_704
    assert job.probe.facts["pattern"] == "W-WE*E"
    assert job.probe.config["layer_types"] == list(afmoe.PROBE_LAYER_TYPES)
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_26b_a3b():
    """The issue's count of the uncut model: 26.12 B parameters, 3.06 B of
    them active a token (the embedding a lookup, so the head alone)."""
    attention, dense = 27_263_232 + 8_192, 37_748_736
    expert, router = 6_291_456, 262_144
    head = 2 * 200192 * 2048
    whole = (32 * attention + 2 * dense
             + 30 * (128 * expert + expert + router) + head + 2048)
    assert whole / 1e9 == pytest.approx(26.12, abs=0.01)
    active = (32 * attention + 2 * dense + 30 * (9 * expert + router)
              + head // 2)
    assert active / 1e9 == pytest.approx(3.06, abs=0.01)
    kinds = [afmoe.FULL if i % 4 == 3 else afmoe.WINDOWED for i in range(32)]
    pattern = afmoe.layer_pattern(kinds, 2)
    assert (pattern.count("W"), pattern.count("*"), pattern.count("-"),
            pattern.count("E")) == (24, 8, 2, 30)
    assert pattern[:16] == "W-W-WE*EWEWEWE*E"


def test_configuration_keeps_the_sources_values():
    """Every value of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: both dense layers and at
    least four more, 8 routed experts, an eighth of the vocabulary; and
    the five things the catalog's config has no key for under ``assumed``."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "trinity-mini")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"}
    published = config["published"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert config[key] != published[key], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                continue
            assert key in config and config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][:8]
        assert {k: row["config"][k] for k in (
            "num_hidden_layers", "num_experts", "vocab_size")} == {
                k: published[k] for k in (
                    "num_hidden_layers", "num_experts", "vocab_size")}
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["route_scale"]) == (2048, 32, 4, 128, 2048, 6144, 1024, 8,
                                       2.826)
    assert config["layer_types"] == [
        afmoe.FULL if i % 4 == 3 else afmoe.WINDOWED for i in range(8)]
    assert config["num_hidden_layers"] - config["num_dense_layers"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "departures"):
        assert config[key]
    assert "16 chips share each layer" in config["deployment"]
    assert {"mup_enabled", "attention_gate", "head_norms",
            "full_layers_unturned", "norms_after_the_mixers"} <= set(
                config["assumed"])


@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("topk_group", 4), ("score_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("tie_word_embeddings", True), ("model_type", "deepseek_v3"),
    ("layer_types", ["sliding_attention"] * 7),
    ("layer_types", ["chunked_attention"] * 8)])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        afmoe.build({**_config(), key: value}, CELL)


def test_the_reference_masks_by_positions_and_holds_a_separate_gate():
    """The mask is built from positions (``s <= t`` and ``t - s <
    window``); the windowed mixer at a window no shorter than the sequence
    is the full one but for the rotary; and the gate is a projection of
    its own: zeroing the gate's columns halves the output."""
    at = jnp.array([0, 3, 9])
    assert reference.seen(at, 10, None).sum(-1).tolist() == [1, 4, 10]
    assert reference.seen(at, 10, 4).sum(-1).tolist() == [1, 4, 4]
    assert reference.seen(at, 10, 4)[2].tolist() == [False] * 6 + [True] * 4
    keys = jax.random.split(jax.random.key(0), 6)
    normal = lambda key, *shape: jax.random.normal(key, shape)
    p = {"q": {"kernel": normal(keys[0], 16, 4, 16)},
         "k": {"kernel": normal(keys[1], 16, 2, 8)},
         "v": {"kernel": normal(keys[2], 16, 2, 8)},
         "o": {"kernel": normal(keys[3], 4, 8, 16)},
         "q_norm": {"scale": jnp.ones(8)}, "k_norm": {"scale": jnp.ones(8)}}
    u = normal(keys[4], 1, 12, 16)
    config = {"rms_norm_eps": 1e-5, "rope_theta": 1e4, "sliding_window": 5}
    full = reference.mixer(u, p, config, False)
    windowed = reference.mixer(u, p, config, True)
    # the first position sees itself alone, and a turn by angle 0 is none
    np.testing.assert_allclose(full[0, 0], windowed[0, 0], rtol=1e-5)
    assert float(jnp.abs(full[0, 6:] - windowed[0, 6:]).max()) > 1e-3
    ungated = {**p, "q": {"kernel": p["q"]["kernel"].at[..., 8:].set(0.0)}}
    halved = reference.mixer(u, ungated, config, False)
    only_gate_moved = {**p, "q": {"kernel": p["q"]["kernel"].at[
        ..., 8:].multiply(-1.0)}}
    other = reference.mixer(u, only_gate_moved, config, False)
    # sigmoid(0) = 1/2, and sigmoid(g) + sigmoid(-g) = 1
    np.testing.assert_allclose(full + other, 2 * halved, rtol=1e-4,
                               atol=1e-5)
    assert "horovod_tpu" not in open(reference.__file__).read().split(
        '"""', 2)[2]


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP products are a windowed layer's products over positions, block
    1's a norm after the mixer."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/", "/block_0/attn/attn_core/attn_window/"),
             ("/block_1/mlp/down/", "/block_1/post_norm/"))

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
    read = lambda name, run: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, run)
    assert all(ms > 0 for ms in by_scope.values())
    assert read("attn_window_core_ms", {}) == pytest.approx(
        by_scope["/block_0/mlp/up/"])
    assert read("post_norm_ms", {}) == pytest.approx(
        by_scope["/block_1/mlp/down/"])
    # the windowed scope is inside the core's: attn_core_ms reads it too
    assert read("attn_core_ms", {}) == pytest.approx(
        by_scope["/block_0/mlp/up/"])
    # the recording holds no Pallas call: no kernel under the scope, so the
    # roofline is left out (and would be with the shapes given, too)
    job = afmoe.build(_config(), CELL)
    run = lambda: {"facts": job.facts, "peak": flops.peaks("TPU v5 lite")}
    assert attn_window_roofline.kernels_ms(trace, {}) is None
    assert read("attn_window_roofline", run()) is None
    # a kernel event under the scope is counted; one of a full layer, under
    # the core's scope alone, and one of an expert layer are not
    device = trace.devices[0]
    lo, hi, steps = trace.window(device)
    call = ('%attn.1 = bf16[2] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    span = (hi - lo) / 4
    device.ops.extend([
        xplane.Op(call.replace("attn.1", name), lo, lo + span, "kernel")
        for name in ("attn.1", "attn.2", "gmm.2")])
    named = regions.name_stacks("x")
    stack = "jit(step)/jvp(afmoe)/block_%s"
    monkeypatch.setattr(regions, "name_stacks", lambda p: {
        **named,
        "attn.1": stack % "0/attn/attn_core/attn_window/hvt_flash_fwd",
        "attn.2": stack % "6/attn/attn_core/hvt_flash_fwd",
        "gmm.2": stack % "5/moe/moe_experts/gmm"})
    assert attn_window_roofline.kernels_ms(trace, {}) == pytest.approx(
        span / steps / 1e6)
    share = read("attn_window_roofline", run())
    assert share == pytest.approx(100 * 70.6403 / (span / steps / 1e6),
                                  rel=1e-4)
    assert read("attn_window_roofline",
                {"facts": {}, "peak": flops.peaks("TPU v5 lite")}) is None
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
            f"chipbench.layer_metrics.{name}").read(trace, run()) is None


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "trinitymini-s16384", "--seed", "2147483999", "--seconds", "1",
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
                  "trained_first_windowed_mixer_vs_reference_by_query_blocks",
                  "trained_first_windowed_float32_parts_with_float32_products",
                  "trained_last_windowed_mixer_vs_reference_by_query_blocks",
                  "trained_last_windowed_float32_parts_with_float32_products",
                  "trained_first_full_mixer_vs_reference_by_query_blocks",
                  "trained_first_full_float32_parts_with_float32_products"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    assert any("rows on the experts held" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {*READERS, "moe_ms", "lm_head_ms"}
