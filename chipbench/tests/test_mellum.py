"""The ``mellum`` family: its FLOP and parameter counts against a hand
count and the package's tree; the products over positions a step requires
of its windowed layers; its configuration against the catalog's entry;
``facts`` carrying what the readers read; ``attn_rope_ms`` on a recorded
trace whose names are rewritten; the reference's two rotaries, its mask and
its share of an expert layer; and the cell's rehearsal."""

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
from chipbench.families import mellum
from chipbench.layer_metrics import moe_experts_roofline
from chipbench.reference import mellum as reference
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 16384, "per_chip_batch": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_experts", "vocab_size"}


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/mellum2-12b-a2.5b.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand: 283.2 M multiply-adds, 1.699 GFLOP a token, 27.8 TFLOP a step,
    attention 62% of it, the experts 18%, the head 20%."""
    projections = 2304 * (2 * 32 + 2 * 4) * 128
    assert projections == 21_233_664
    assert mellum.band_pairs(16384, 1024) == 16_253_440 == (
        1024 * 1025 // 2 + (16384 - 1024) * 1024)
    windowed = 32 * 256 * 16_253_440 / 16384
    full = 32 * 256 * 16385 / 2
    assert windowed == 8_126_720 and full == 67_112_960
    experts = 2304 * 64 + 8 * 16 / 64 * 3 * 2304 * 896
    assert experts == 147_456 + 2 * 6_193_152 == 12_533_760
    head = 24576 * 2304
    job = mellum.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"W": 3 * (projections + windowed),
                    "*": projections + full, "E": 4 * experts, "head": head}
    total = sum(macs.values())
    assert total == 283_185_920
    assert job.flops_per_item == 6.0 * total == 1_699_115_520.0
    assert job.items_per_step_per_chip == 16384
    assert job.flops_per_item * 16384 / 1e12 == pytest.approx(27.84, abs=0.01)
    assert (macs["W"] + macs["*"]) / total == pytest.approx(0.62, abs=0.005)
    assert 4 * projections / total == pytest.approx(0.30, abs=0.005)
    assert full / total == pytest.approx(0.24, abs=0.005)
    assert 3 * windowed / total == pytest.approx(0.09, abs=0.005)
    assert macs["E"] / total == pytest.approx(0.18, abs=0.005)
    assert head / total == pytest.approx(0.20, abs=0.005)
    # the band of a query block of 512 is 1,535 keys over tiles of 1,024
    assert 16_253_440 / (16384 * 16385 / 2) == pytest.approx(0.121, abs=0.001)


def test_products_over_positions_the_windowed_layers_require():
    """1,152 multiply-adds a pair of the band under remat, the pairs
    counted exactly: 18.25 ms a step for three layers at 197 TFLOP/s,
    compute-bound."""
    job = mellum.build(_config(), CELL)
    pairs = 32 * 16_253_440
    macs = 3 * pairs * 128 * 9
    nbytes = 3 * 16384 * (2 * 18_560 + 37_120)
    assert job.facts["attn_window"] == {
        "layers": 3, "batch": 1, "heads": 32, "kv_heads": 4,
        "seq_len": 16384, "head_dim": 128, "window": 1024,
        "band_pairs": 16_253_440, "macs_per_step": float(macs),
        "bytes_per_step": float(nbytes)}
    seconds, bound = flops.roofline_seconds(
        2.0 * macs, nbytes, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * seconds == pytest.approx(
        18.25, abs=0.01)


def test_facts_carry_what_the_readers_read():
    job = mellum.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 4, "rows": 32768, "experts": 16, "d_model": 2304,
        "d_expert": 896, "itemsize": 2, "row_bound": 131072,
        "routed_over": 64}
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "WEWEWE*E"
    least, bound = moe_experts_roofline.least_ms(
        job.facts, flops.peaks("TPU v5 lite"))
    # 48 products of 2 x 32,768 x 2304 x 896 FLOP at 197 TFLOP/s
    assert bound == "compute" and least == pytest.approx(32.96, abs=0.05)


def test_parameters_of_the_cut_are_the_trees():
    """595,153,152 parameters, 8.87 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order, as the issue counted it."""
    job = mellum.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 595_153_152
    assert 16 * size(params) / 1e9 == pytest.approx(9.52, abs=0.005)
    assert 16 * size(params) / 2 ** 30 == pytest.approx(8.87, abs=0.005)
    assert extra == {}
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(8)]
    assert kinds == ["attn", "moe"] * 4
    attn = params["block_6"]["attn"]
    assert jax.tree.map(lambda a: a.shape, attn) == {
        "q": {"kernel": (2304, 32, 128)}, "k": {"kernel": (2304, 4, 128)},
        "v": {"kernel": (2304, 4, 128)}, "o": {"kernel": (32, 128, 2304)}}
    assert size(attn) == 21_233_664 == size(params["block_0"]["attn"])
    moe = params["block_1"]["moe"]
    assert moe["router"].shape == (2304, 64)
    assert moe["up"].shape == moe["gate"].shape == (16, 2304, 896)
    assert moe["down"].shape == (16, 896, 2304)
    assert set(moe) == {"router", "gate", "up", "down"}
    assert size(moe) == 147_456 + 16 * 6_193_152 == 99_237_888
    assert params["lm_head"].shape == params["embedding"].shape == (24576,
                                                                    2304)
    assert 4 * (21_233_664 + 99_237_888 + 4_608) + 2 * 56_623_104 + 2_304 \
        == 595_153_152
    assert job.probe.facts["pattern"] == "WE*E"
    assert job.probe.config["layer_types"] == list(mellum.PROBE_LAYER_TYPES)
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_12b_a2_5b():
    """The issue's count of the uncut model: 12.15 B parameters, 2.44 B of
    them active a token (8 of a layer's 64 experts; embedding and head
    both counted, as the issue does)."""
    attention, expert, router, norms = 21_233_664, 6_193_152, 147_456, 4_608
    layer = attention + router + 64 * expert + norms
    assert layer == 417_747_456
    whole = 28 * layer + 2 * 98304 * 2304 + 2304
    assert whole == 12_149_915_904
    active = (28 * (attention + router + norms + 8 * expert)
              + 2 * 98304 * 2304)
    assert active / 1e9 == pytest.approx(2.44, abs=0.01)
    kinds = [mellum.FULL if i % 4 == 3 else mellum.WINDOWED
             for i in range(28)]
    pattern = mellum.layer_pattern(kinds)
    assert (pattern.count("W"), pattern.count("*"), pattern.count("E")) == (
        21, 7, 28)
    assert pattern[:8] == "WEWEWE*E"


def test_configuration_keeps_the_sources_values():
    """Every value of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: a whole period of four
    layers, 8 routed experts, an eighth of the vocabulary; and what the
    catalog's config has no key for under ``assumed``."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "mellum2-12b-a2.5b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    published = config["published"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert config[key] != published[key], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            if key in REDUCED:
                continue
            assert key in config and config[key] == value, key
        assert config["layer_types"] == row["config"]["layer_types"][:4]
        assert config["mlp_layer_types"] == row["config"][
            "mlp_layer_types"][:4]
        assert {k: row["config"][k] for k in (
            "num_hidden_layers", "num_experts", "vocab_size")} == {
                k: published[k] for k in (
                    "num_hidden_layers", "num_experts", "vocab_size")}
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["sliding_window"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["norm_topk_prob"]) == (
                2304, 32, 4, 128, 1024, 896, 8, True)
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert config["layer_types"] == [mellum.WINDOWED] * 3 + [mellum.FULL]
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["num_experts"] * 4 == published["num_experts"]
    assert config["vocab_size"] * 4 == published["vocab_size"]
    for key in ("deployment", "departures"):
        assert config[key]
    assert "4 chips share each layer" in config["deployment"]
    assert {"head_norms", "multi_token_prediction", "auxiliary_loss",
            "optimizer", "dtype", "seq_len"} <= set(config["assumed"])
    cfg = mellum._model_config(config, 16384)
    assert (cfg.layer_pattern, cfg.rotary, cfg.rotary_base, cfg.attn_window,
            cfg.experts_held, cfg.head_norm, cfg.moe_renormalise) == (
                "WEWEWE*E", True, 500000.0, 1024, (0, 16), False, True)
    assert cfg.rotary_scaling == (500000.0, 16.0, 8192, 32.0, 1.0,
                                  1.2772588722239782, True)


@pytest.mark.parametrize("key, value", [
    ("model_type", "afmoe"), ("tie_word_embeddings", True),
    ("attention_bias", True), ("use_sliding_window", False),
    ("layer_types", ["sliding_attention"] * 3),
    ("layer_types", ["chunked_attention"] * 4),
    ("mlp_layer_types", ["dense", "sparse", "sparse", "sparse"]),
    ("rope_parameters", {"full_attention": {"rope_type": "default",
                                            "rope_theta": 500000}}),
])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        mellum.build({**_config(), key: value}, CELL)


@pytest.mark.parametrize("entry, changes, match", [
    ("full_attention", {"rope_type": "llama3"}, "rope_type 'llama3'"),
    ("full_attention", {"mscale_all_dim": 1.0}, "mscale_all_dim"),
    ("sliding_attention", {"rope_type": "yarn", "factor": 16,
                           "original_max_position_embeddings": 8192},
     "windowed layers turn by the plain one"),
])
def test_family_refuses_a_law_that_is_not_built(entry, changes, match):
    config = _config()
    ropes = {**config["rope_parameters"],
             entry: {**config["rope_parameters"][entry], **changes}}
    with pytest.raises(ValueError, match=match):
        mellum.build({**config, "rope_parameters": ropes}, CELL)


def test_the_reference_turns_two_ways_masks_by_positions_and_is_alone():
    """The mask is built from positions; the YaRN entry's frequencies are
    the equations' (``low`` 18, ``high`` 35, three regimes) with the
    factor on the turned vectors; the first position is turned by angle 0
    under either law, so there the two kinds differ by the factor alone;
    and the file imports nothing of the package."""
    at = jnp.array([0, 3, 9])
    assert reference.seen(at, 10, None).sum(-1).tolist() == [1, 4, 10]
    assert reference.seen(at, 10, 4).sum(-1).tolist() == [1, 4, 4]
    ropes = _config()["rope_parameters"]
    assert reference.yarn_range(ropes[mellum.FULL], 128) == (18, 35)
    plain, one = reference.thetas(ropes[mellum.WINDOWED], 128)
    scaled, factor = reference.thetas(ropes[mellum.FULL], 128)
    assert (one, factor) == (1.0, 1.2772588722239782)
    ratio = np.asarray(scaled / plain)
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)
    np.testing.assert_allclose(
        ratio[19:35], 1 - (np.arange(19, 35) - 18) / 17 * 15 / 16, rtol=1e-6)
    x = jax.random.normal(jax.random.key(0), (12, 2, 128))
    turned = reference.rotary_halves(x, ropes[mellum.FULL])
    np.testing.assert_allclose(turned[0], factor * x[0], rtol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), factor * jnp.linalg.norm(x, axis=-1),
        rtol=1e-5)
    np.testing.assert_allclose(
        reference.rotary_halves(x, ropes[mellum.WINDOWED])[0], x[0])
    assert "horovod_tpu" not in open(reference.__file__).read().split(
        '"""', 2)[2]


def test_the_references_shares_add_up_to_its_whole_layer():
    """The reference given a share (``experts_held_first`` and the share's
    stacks) sums the held experts' terms alone: four shares of 16 add up
    to the layer over all 64."""
    keys = jax.random.split(jax.random.key(0), 5)
    normal = lambda key, *shape: jax.random.normal(key, shape)
    p = {"router": normal(keys[0], 16, 64), "gate": normal(keys[1], 64, 16, 6),
         "up": normal(keys[2], 64, 16, 6), "down": normal(keys[3], 64, 6, 16)}
    h = normal(keys[4], 24, 16)
    config = {"num_experts_per_tok": 8, "norm_topk_prob": True}
    whole, routing = reference.experts(h, p, config)
    parts = sum(reference.experts(
        h, {**p, **{n: p[n][first:first + 16] for n in ("gate", "up",
                                                         "down")}},
        {**config, "experts_held_first": first})[0]
        for first in range(0, 64, 16))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-4)
    assert routing["own"].shape == (24, 8)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP products are a windowed layer's rotary, block 1's a full
    layer's."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/", "/block_0/attn/attn_rope/"),
             ("/block_1/mlp/down/", "/block_6/attn/attn_rope/"))

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


def test_attn_rope_ms_reads_its_scope_or_nothing(renamed, monkeypatch):
    trace, by_scope = renamed
    read = lambda name, run: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, run)
    assert all(ms > 0 for ms in by_scope.values())
    # both kinds of layer's rotary, one number
    assert read("attn_rope_ms", {}) == pytest.approx(sum(by_scope.values()))
    # ... which the elementwise passes' reader holds too
    assert read("attn_elementwise_ms", {}) == pytest.approx(
        sum(by_scope.values()))
    # the parent's program has no such scope in a model without a rotary:
    # left out, not 0, and nothing raised; so too without a device plane
    monkeypatch.undo()
    module = importlib.import_module("chipbench.layer_metrics.attn_rope_ms")
    assert module.read(None, {}) is None
    monkeypatch.setattr(regions, "name_stacks", lambda p: {"op": "jit(f)/x"})
    monkeypatch.setattr(regions, "trace_file", lambda *a: "somewhere")
    assert module.read(trace, {}) is None
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == "attn_rope_ms")
    assert (module.UNIT, module.LAYER, module.MOVES) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["workloads"] == ["mellum2-s16384"]


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "mellum2-s16384", "--seed", "2147483999", "--seconds", "1",
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
                  "trained_first_full_float32_parts_with_float32_products",
                  "trained_last_experts_vs_reference_given_experts"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    # the rehearsal's shares expect two rows a token and work in rounds of
    # three, as the cell's do
    rounds = next(l for l in lines if "rows on the experts held" in l)
    assert "in rounds of 192:" in rounds and "round(s)" in rounds
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {"attn_rope_ms", "moe_ms",
                                         "lm_head_ms"}
