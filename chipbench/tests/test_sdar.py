"""The ``sdar`` family: its FLOP and parameter counts against a hand count
and the package's tree; the visible pairs of the block-causal rule against
a brute count of the mask; the products over positions a step requires;
its configuration against the catalog's entry and ``BENCHMARK.json``;
``facts`` carrying what the readers read; the three ``attn_blocks_*``
readers on a recorded trace whose names are rewritten; the reference's
mask, its positions and its share of an expert layer; and the cell's
rehearsal."""

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

from chipbench import flops, kernel_calls, regions, xplane
from chipbench.families import sdar
from chipbench.layer_metrics import moe_experts_roofline
from chipbench.reference import sdar as reference
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 8192, "per_chip_batch": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/sdar-30b-a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("length, size", [(32, 4), (64, 8), (24, 1), (16, 16)])
def test_visible_pairs_against_a_brute_count_of_the_mask(length, size):
    """The family's formula and the reference's mask, pair by pair: clean
    rows ``B^2 n (n + 1) / 2``, noised rows ``B^2 n (n - 1) / 2 + L B``, a
    quarter of ``(2 L)^2`` as the blocks shrink; no noised row sees a clean
    key of its own block or a later one, and no row a noised key of
    another block."""
    seen = np.asarray(reference.seen(jnp.arange(2 * length), length, size))
    pairs = sdar.visible_pairs(length, size)
    assert seen[:length].sum() == pairs["clean"]
    assert seen[length:].sum() == pairs["noised"]
    n = length // size
    assert pairs == {"clean": size * size * n * (n + 1) // 2,
                     "noised": size * size * n * (n - 1) // 2 + length * size}
    assert sum(pairs.values()) == length * length + length * size
    block = np.arange(length) // size
    assert not seen[:length, length:].any()         # clean rows, noised keys
    assert not (seen[length:, :length] & (block[None] >= block[:, None])).any()
    assert (seen[length:, length:] == (block[None] == block[:, None])).all()
    assert (seen[:length, :length] == (block[None] <= block[:, None])).all()


def test_flops_per_data_token_of_the_cut_by_hand():
    """One chip's share at the published widths by hand: 672.4 M
    multiply-adds a data token, 4.03 GFLOP, 33.0 TFLOP a step; an item is
    a data token, 8,192 a step."""
    projections = 2048 * (2 * 32 + 2 * 4) * 128
    assert projections == 18_874_368
    pairs = sdar.visible_pairs(8192, 4)
    assert pairs == {"clean": 16 * 2048 * 2049 // 2,
                     "noised": 16 * 2048 * 2047 // 2 + 8192 * 4}
    assert sum(pairs.values()) == 67_141_632
    assert sum(pairs.values()) / (2 * 8192) ** 2 == pytest.approx(
        0.25, abs=0.0005)
    over_positions = 67_141_632 / 8192 * 32 * 256
    assert over_positions == 67_141_632
    experts = 2048 * 128 + 8 * 16 / 128 * 3 * 2048 * 768
    assert experts == 262_144 + 4_718_592
    head = 18992 * 2048
    last_clean = (2048 * 2 * 32 * 128 + 32 * 256 * pairs["clean"] / 8192
                  + experts)
    job = sdar.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"*": 6 * (2 * projections + over_positions),
                    "E": 6 * 2 * experts, "last_clean": -last_clean,
                    "head": head}
    assert macs["*"] + macs["E"] == pytest.approx(688.9e6, rel=1e-3)
    total = sum(macs.values())
    assert total == pytest.approx(672.4e6, rel=1e-3)
    assert job.flops_per_item == 6.0 * total
    assert job.items_per_step_per_chip == 8192 and job.item == "tokens"
    assert job.flops_per_item * 8192 / 1e12 == pytest.approx(33.05, abs=0.05)
    assert 6 * over_positions / (total - macs["last_clean"]) == pytest.approx(
        0.55, abs=0.01)


def test_products_over_positions_a_step_requires():
    """1,152 multiply-adds a pair under remat, the pairs exactly, the last
    layer's clean rows left out: 138.2 ms a step at 197 TFLOP/s,
    compute-bound; the bytes are two kernel calls' a layer."""
    job = sdar.build(_config(), CELL)
    pairs = sdar.visible_pairs(8192, 4)
    required = 32 * (6 * 67_141_632 - pairs["clean"])
    macs = required * 128 * 9
    nbytes = 12 * 8192 * (2 * 18_560 + 37_120)
    assert job.facts["attn_blocks"] == {
        "layers": 6, "batch": 1, "heads": 32, "kv_heads": 4,
        "seq_len": 8192, "head_dim": 128, "block": 4,
        "visible_pairs": pairs, "macs_per_step": float(macs),
        "bytes_per_step": float(nbytes)}
    seconds, bound = flops.roofline_seconds(
        2.0 * macs, nbytes, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * seconds == pytest.approx(
        138.2, abs=0.05)


def test_facts_carry_what_the_readers_read():
    job = sdar.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 6, "rows": 16384, "experts": 16, "d_model": 2048,
        "d_expert": 768, "itemsize": 2, "row_bound": 131072,
        "routed_over": 128}
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "*E" * 6
    least, bound = moe_experts_roofline.least_ms(
        job.facts, flops.peaks("TPU v5 lite"))
    # 72 products of 2 x 16,384 x 2048 x 768 FLOP at 197 TFLOP/s
    assert bound == "compute" and least == pytest.approx(18.84, abs=0.05)


def test_parameters_of_the_cut_are_the_trees():
    """645.6 M parameters, 10.33 GB at 16 bytes each: the count from shapes
    is the tree ``models.GPT`` builds."""
    job = sdar.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 645_623_296
    assert 16 * size(params) / 1e9 == pytest.approx(10.33, abs=0.005)
    assert extra == {}
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(12)]
    assert kinds == ["attn", "moe"] * 6
    attn = params["block_10"]["attn"]
    assert jax.tree.map(lambda a: a.shape, attn) == {
        "q": {"kernel": (2048, 32, 128)}, "k": {"kernel": (2048, 4, 128)},
        "v": {"kernel": (2048, 4, 128)}, "o": {"kernel": (32, 128, 2048)},
        "q_norm": {"scale": (128,)}, "k_norm": {"scale": (128,)}}
    assert size(attn) == 18_874_368 + 256
    moe = params["block_1"]["moe"]
    assert moe["router"].shape == (2048, 128)
    assert moe["up"].shape == moe["gate"].shape == (16, 2048, 768)
    assert moe["down"].shape == (16, 768, 2048)
    assert set(moe) == {"router", "gate", "up", "down"}
    assert size(moe) == 262_144 + 16 * 4_718_592 == 75_759_616
    assert params["lm_head"].shape == params["embedding"].shape == (18992,
                                                                    2048)
    assert job.probe.facts["pattern"] == "*E*E"
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])
    # the whole model: 30.5 B, 3.3 B of them active a token
    layer = 18_874_624 + 262_144 + 128 * 4_718_592 + 4096
    assert 48 * layer + 2 * 151936 * 2048 + 2048 == pytest.approx(
        30.53e9, rel=1e-3)
    active = 48 * (18_874_624 + 262_144 + 8 * 4_718_592) + 2 * 151936 * 2048
    assert active / 1e9 == pytest.approx(3.35, abs=0.05)


def test_batch_is_the_packages_noising():
    """Ids below the mask id, the mask id in the noised half alone, about
    half the positions masked, weights ``1 / t`` there; the same key gives
    the same batch."""
    job = sdar.build(_config(), CELL)
    batch = jax.jit(lambda k: job.make_batch(k, 1))(jax.random.key(2147600000))
    tokens, weights = np.asarray(batch["tokens"]), np.asarray(batch["weights"])
    assert tokens.shape == (1, 16384) and weights.shape == (1, 8192)
    assert tokens[:, :8192].max() < 18991
    np.testing.assert_array_equal(tokens[:, :8192], batch["targets"])
    masked = tokens[:, 8192:] == 18991
    np.testing.assert_array_equal(masked, weights > 0)
    assert 0.45 < masked.mean() < 0.55
    assert weights[masked].min() >= 1.0 and weights.max() <= 1000.0 + 1e-3
    again = jax.jit(lambda k: job.make_batch(k, 1))(jax.random.key(2147600000))
    np.testing.assert_array_equal(tokens, again["tokens"])


def test_the_mask_tokens_experts_are_placed_one_on_the_share():
    """Of the experts the mask token's own embedding prefers in a layer,
    one is held and the other held slots go to those it prefers least,
    whatever the seed; the columns are a permutation of what was drawn."""
    config = {**_config(), **sdar.REHEARSAL["config"]}
    cfg = sdar._model_config(config, 64)
    job = sdar.build(config, sdar.REHEARSAL["traffic"])
    model_init = jax.jit(lambda key: sdar.GPT(cfg).init(
        key, jnp.zeros((1, 128), jnp.int32))["params"])
    first, count = cfg.experts_held
    for seed in range(4):
        params, _ = jax.jit(job.init)(jax.random.key(seed))
        token = np.asarray(params["embedding"][config["mask_token_id"]])
        drawn = model_init(jax.random.key(seed))
        for name in ("block_1", "block_3"):
            router = np.asarray(params[name]["moe"]["router"])
            ranks = np.argsort(-(token @ router))
            chosen = set(ranks[:cfg.experts_per_token].tolist())
            held = set(range(first, first + count))
            assert len(chosen & held) == 1 and ranks[0] in held
            assert held - {ranks[0]} == set(ranks[-(count - 1):].tolist())
            was = np.asarray(drawn[name]["moe"]["router"])
            assert sorted(map(tuple, router.T)) == sorted(map(tuple, was.T))


def test_configuration_keeps_the_sources_values():
    """Every value of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: four layers, 8 routed
    experts, an eighth of the vocabulary; and what the catalog's config
    has no key for under ``assumed``."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["source"] == config["source"]
    assert entry["file"] == "chipbench/configs/sdar-30b-a3b.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert set(config["reduced"]) == REDUCED
    cell = next(w for w in bench["workloads"] if w["name"] == "sdar-s8192")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b", "b1-s8192-bd4", 1)
    assert len(bench["workloads"]) == 14
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = {m["name"] for group in ("end_to_end", "per_layer")
              for m in bench[group] if "sdar-s8192" in m.get("workloads", [])}
    assert listed == {
        "tok_s_chip", "recompute_ms", "lm_head_ms", "moe_ms",
        "moe_experts_ms", "moe_shuffle_ms", "moe_experts_roofline",
        "moe_rounds", "grouped_ms", "attn_ms", "attn_core_ms",
        "attn_elementwise_ms", "attn_rope_ms", "flash_fwd_ms",
        "flash_bwd_ms", "attn_blocks_core_ms", "attn_blocks_roofline",
        "attn_blocks_merge_ms"}
    published = config["published"]
    for key in REDUCED:
        assert config[key] != published[key], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            if key in REDUCED:
                continue
            assert key in config and config[key] == value, key
        assert {k: row["config"][k] for k in REDUCED} == {
            k: published[k] for k in REDUCED}
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["norm_topk_prob"], config["rope_theta"],
            config["rms_norm_eps"]) == (
                2048, 32, 4, 128, 768, 8, True, 1000000, 1e-6)
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["num_experts"] * 8 == published["num_experts"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["mask_token_id"] == config["vocab_size"] - 1
    for key in ("deployment", "departures"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"]
    assert {"block_length", "noise_schedule", "targets", "mask_token_id",
            "auxiliary_loss", "optimizer", "dtype", "seq_len"} <= set(
                config["assumed"])
    cfg = sdar._model_config(config, 8192)
    assert (cfg.layer_pattern, cfg.rotary, cfg.rotary_base, cfg.head_norm,
            cfg.experts_held, cfg.moe_renormalise, cfg.diffusion_block,
            cfg.n_experts, cfg.experts_per_token, cfg.tie_embeddings) == (
                "*E" * 6, True, 1e6, True, (0, 16), True, 4, 128, 8, False)


@pytest.mark.parametrize("key, value", [
    ("model_type", "qwen3_moe"), ("tie_word_embeddings", True),
    ("attention_bias", True), ("use_sliding_window", True),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("rope_scaling", {"rope_type": "yarn"}), ("mask_token_id", 18992),
])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        sdar.build({**_config(), key: value}, CELL)


def test_the_reference_turns_both_copies_at_their_position_and_is_alone():
    """Both copies of position ``i`` are turned at ``i`` (the reference's
    attention of two identical copies' keys is what it is of one: row ``L
    + i`` and row ``i`` get the same q and k), and the file imports
    nothing of the package."""
    x = jax.random.normal(jax.random.key(0), (8, 2, 16))
    at = jnp.arange(8) % 4
    turned = reference.rotary_halves(x, at, 1e6)
    np.testing.assert_allclose(turned[0], x[0])
    np.testing.assert_allclose(turned[4], x[4])
    np.testing.assert_allclose(
        jnp.linalg.norm(turned, axis=-1), jnp.linalg.norm(x, axis=-1),
        rtol=1e-5)
    twice = reference.rotary_halves(jnp.concatenate([x[:4], x[:4]]), at, 1e6)
    np.testing.assert_array_equal(twice[:4], twice[4:])
    assert "horovod_tpu" not in open(reference.__file__).read().split(
        '"""', 2)[2]


def test_the_references_shares_add_up_to_its_whole_layer():
    """The reference given a share sums the held experts' terms alone:
    eight shares of 16 add up to the layer over all 128."""
    from chipbench.reference import mellum

    keys = jax.random.split(jax.random.key(0), 5)
    normal = lambda key, *shape: jax.random.normal(key, shape)
    p = {"router": normal(keys[0], 16, 128),
         "gate": normal(keys[1], 128, 16, 6),
         "up": normal(keys[2], 128, 16, 6),
         "down": normal(keys[3], 128, 6, 16)}
    h = normal(keys[4], 24, 16)
    config = {"num_experts_per_tok": 8, "norm_topk_prob": True}
    assert reference.experts_layer is mellum.experts_layer
    whole, routing = mellum.experts(h, p, config)
    parts = sum(mellum.experts(
        h, {**p, **{n: p[n][first:first + 16] for n in ("gate", "up",
                                                         "down")}},
        {**config, "experts_held_first": first})[0]
        for first in range(0, 128, 16))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-4)
    assert routing["own"].shape == (24, 8)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP products are a diffusion layer's own-block products, block 1's
    its merge."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/",
              "/block_0/attn/attn_core/attn_blocks/attn_blocks_own/"),
             ("/block_1/mlp/down/",
              "/block_2/attn/attn_core/attn_blocks/attn_blocks_merge/"))

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


def test_attn_blocks_readers_read_their_scope_or_nothing(renamed,
                                                         monkeypatch):
    trace, by_scope = renamed
    read = lambda name, run: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, run)
    assert all(ms > 0 for ms in by_scope.values())
    whole = sum(by_scope.values())
    assert read("attn_blocks_core_ms", {}) == pytest.approx(whole)
    # the recording's events under the scope are XLA's, none a Pallas call:
    # all of it is what XLA puts around the kernels, and the roofline has
    # no kernel to read
    assert read("attn_blocks_merge_ms", {}) == pytest.approx(whole)
    assert read("attn_core_ms", {}) == pytest.approx(whole)
    job = sdar.build(_config(), CELL)
    run = {"facts": job.facts, "peak": flops.peaks("TPU v5 lite")}
    assert read("attn_blocks_roofline", run) is None
    # ... and with the events under the scope counted as kernels, the least
    # time of the cell's products over them
    found = kernel_calls.window(trace, run)
    as_kernels = kernel_calls.Window(found.steps, tuple(
        kernel_calls.Event(e.base, e.name, "/attn_blocks/" in e.part, e.part,
                           e.region, e.ns) for e in found.events))
    run[kernel_calls.KEPT] = (trace, as_kernels)
    assert read("attn_blocks_roofline", run) == pytest.approx(
        100.0 * 138.204 / whole, rel=1e-3)
    assert read("attn_blocks_merge_ms", run) is None
    # the parent's program has no such scope: left out, not 0, and nothing
    # raised; so too without a device plane
    monkeypatch.undo()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("attn_blocks_core_ms", "attn_blocks_roofline",
                 "attn_blocks_merge_ms"):
        module = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert module.read(None, {}) is None
        with monkeypatch.context() as m:
            m.setattr(regions, "name_stacks", lambda p: {"op": "jit(f)/x"})
            m.setattr(regions, "trace_file", lambda *a: "somewhere")
            assert module.read(trace, {"facts": job.facts,
                                       "peak": run["peak"]}) is None
        entry = entries[name]
        assert (module.UNIT, module.LAYER, module.MOVES) == (
            entry["unit"], entry["layer"], entry["moves"])
        assert entry["workloads"] == ["sdar-s8192"]


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "sdar-s8192", "--seed", "2147483999", "--seconds", "1",
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
                  "trained_first_attention_mixer_vs_reference_by_query_blocks",
                  "trained_first_attention_float32_parts_with_float32_products",
                  "trained_last_attention_mixer_vs_reference_by_query_blocks",
                  "trained_last_attention_float32_parts_with_float32_products",
                  "trained_last_experts_vs_reference_given_experts"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    # the rehearsal's share expects one row a program row and works in
    # rounds of two, as the cell's does
    rounds = next(l for l in lines if "rows on the experts held" in l)
    assert "in rounds of 256:" in rounds and "round(s)" in rounds
    assert "positions masked" in rounds
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {"attn_blocks_core_ms", "moe_ms",
                                         "lm_head_ms"}
