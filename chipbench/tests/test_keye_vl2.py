"""The ``keye_vl2`` family: its FLOP and parameter counts against a hand
count and the package's tree; the products a step requires over the chosen
pairs; its configuration against the catalog's entry; ``facts`` carrying
what the readers read; its readers on a recorded trace whose names are
rewritten; the program against the plain reference on seeded weights; and
the cell's rehearsal."""

import gzip
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, regions, xplane
from chipbench.families import keye_vl2
from chipbench.reference import keye_vl2 as reference
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")
CELL = {"seq_len": 16384, "per_chip_batch": 1}
READERS = ("dsa_ms", "dsa_index_ms", "dsa_select_ms", "dsa_core_ms",
           "dsa_core_roofline", "dsa_select_roofline")


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer by hand: the
    chosen pairs and not the causal ones, the index scores over all causal
    pairs; 2.531 GFLOP a token, 41.5 TFLOP a step, the mixer 86% of it."""
    projections = 2048 * (2 * 32 + 2 * 4) * 128
    assert projections == 18_874_368
    indexer = 2048 * (16 * 64 + 64 + 16)
    assert indexer == 2_260_992
    index_scores = 16 * 64 * 16385 / 2
    assert index_scores == 8_389_120
    pairs = keye_vl2.chosen_pairs(16384, 2048)
    assert pairs == 2048 * 2049 // 2 + (16384 - 2048) * 2048 == 31_458_304
    assert pairs / 16384 == pytest.approx(1920.06, abs=0.005)
    chosen = 32 * 256 * pairs / 16384
    assert chosen == pytest.approx(15.73e6, rel=1e-3)
    experts = 2048 * 128 + 8 * 8 / 128 * 3 * 2048 * 768
    assert experts == 262_144 + 2_359_296
    head = 18992 * 2048
    job = keye_vl2.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    mixer = projections + indexer + index_scores + chosen
    assert macs == {"S": 8 * mixer, "E": 8 * experts, "head": head}
    total = sum(macs.values())
    assert total / 1e6 == pytest.approx(421.9, abs=0.05)
    assert job.flops_per_item == 6.0 * total
    assert job.flops_per_item / 1e9 == pytest.approx(2.531, abs=0.001)
    assert job.items_per_step_per_chip == 16384
    assert job.flops_per_item * 16384 / 1e12 == pytest.approx(41.5, abs=0.05)
    assert macs["S"] / total == pytest.approx(0.86, abs=0.005)
    governed = 8 * (indexer + index_scores + chosen) / total
    assert governed == pytest.approx(0.50, abs=0.005)
    # what a program that masks every causal tile executes instead
    assert 32 * 256 * 16385 / 2 == pytest.approx(67.1e6, rel=1e-3)


def test_products_over_the_chosen_pairs_a_step_requires():
    """Two products a chosen pair forward and in the recomputation, five
    backward, at 128: 8 layers x 32 heads x 31,458,304 pairs x 9 x 128;
    compute-bound at the v5e's peaks, 94 ms a step."""
    job = keye_vl2.build(_config(), CELL)
    dsa = job.facts["dsa"]
    assert dsa["chosen_pairs"] == 31_458_304
    assert dsa["core_macs_per_step"] == 8 * 32 * 31_458_304 * 128 * 9
    assert keye_vl2.dsa_core_macs_per_step(
        1, 1, 1, 16384, 128, 2048, remat=False) == 31_458_304 * 128 * 7
    forward = 2 * 128 * (2 * 32 + 2 * 4) + 4 * 32
    backward = 2 * 128 * (4 * 32 + 4 * 4) + 8 * 32
    assert dsa["core_bytes_per_step"] == 8 * 16384 * (2 * forward + backward)
    seconds, bound = flops.roofline_seconds(
        2.0 * dsa["core_macs_per_step"], dsa["core_bytes_per_step"],
        flops.peaks("TPU v5 lite"))
    assert bound == "compute" and 1e3 * seconds == pytest.approx(94.2, abs=0.1)
    assert dsa["select_bytes_per_step"] == 8 * 16384 * 16385 / 2 * 5
    # a short sequence chooses nothing: every causal pair
    assert keye_vl2.chosen_pairs(2048, 2048) == 2048 * 2049 // 2
    assert keye_vl2.chosen_pairs(1024, 2048) == 1024 * 1025 // 2


def test_facts_carry_what_the_readers_read():
    job = keye_vl2.build(_config(), CELL)
    assert job.facts["moe"] == {
        "layers": 8, "rows": 8192, "experts": 8, "d_model": 2048,
        "d_expert": 768, "itemsize": 2, "row_bound": 131072,
        "routed_over": 128}
    assert job.facts["remat"] is True
    assert job.facts["pattern"] == "SE" * 8
    assert {k: job.facts["dsa"][k] for k in (
        "layers", "batch", "heads", "kv_heads", "seq_len", "head_dim",
        "topk")} == {"layers": 8, "batch": 1, "heads": 32, "kv_heads": 4,
                     "seq_len": 16384, "head_dim": 128, "topk": 2048}


def test_parameters_of_the_cut_are_the_trees():
    """550,999,040 parameters, 8.82 GB (8.21 GiB) at 16 bytes each: the
    count from shapes is the tree ``models.GPT`` builds, as the issue
    counted it."""
    job = keye_vl2.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    size = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))
    assert size(params) == job.facts["n_params"] == 550_999_040
    assert 16 * size(params) / 1e9 == pytest.approx(8.82, abs=0.005)
    assert 16 * size(params) / 2 ** 30 == pytest.approx(8.21, abs=0.005)
    assert extra == {}
    kinds = ["".join(sorted(set(params[f"block_{i}"]) - {"norm"}))
             for i in range(16)]
    assert kinds == ["dsa", "moe"] * 8
    dsa = params["block_0"]["dsa"]
    assert {k: v.shape for k, v in dsa.items()} == {
        "q_proj": (2048, 32, 128), "k_proj": (2048, 4, 128),
        "v_proj": (2048, 4, 128), "q_norm": (128,), "k_norm": (128,),
        "o_proj": (32, 128, 2048), "index_q": (2048, 16, 64),
        "index_k": (2048, 64), "index_k_norm": (2, 64),
        "index_w": (2048, 16)}
    indexer = [dsa[k] for k in ("index_q", "index_k", "index_k_norm",
                                "index_w")]
    assert size(indexer) == 2_261_120
    assert size(dsa) - size(indexer) == 18_874_624
    moe = params["block_1"]["moe"]
    assert moe["router"].shape == (2048, 128)
    assert moe["up"].shape == moe["gate"].shape == (8, 2048, 768)
    assert moe["down"].shape == (8, 768, 2048)
    assert set(moe) == {"router", "gate", "up", "down"}
    assert size(moe) == 262_144 + 37_748_736
    assert size((dsa, moe)) + 2 * 2048 == 59_150_720
    assert params["lm_head"].shape == params["embedding"].shape == (18992,
                                                                    2048)
    assert size((params["embedding"], params["lm_head"])) == 77_791_232
    assert job.probe.facts["pattern"] == keye_vl2.PROBE_PATTERN
    assert job.probe.facts["n_params"] == size(
        jax.eval_shape(job.probe.init, jax.random.key(0))[0])


def test_the_whole_model_is_the_names_30b_a3b():
    attention, indexer, router, expert = (18_874_624, 2_261_120, 262_144,
                                          4_718_592)
    whole = (48 * (attention + indexer + router + 128 * expert + 4096)
             + 2 * 151936 * 2048 + 2048)
    assert whole / 1e9 == pytest.approx(30.6, abs=0.1)
    active = (48 * (attention + indexer + router + 8 * expert)
              + 151936 * 2048)
    assert active / 1e9 == pytest.approx(3.2, abs=0.1)
    assert keye_vl2.layer_pattern(8) == "SESESESESESESESE"


def test_configuration_keeps_the_sources_values():
    """Every number of the catalog's entry under its own key but those in
    ``reduced``; nested groups whole; no width among the reduced; the
    floors: four layers, 8 routed experts, an eighth of the vocabulary."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"}
    published = config["published"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
    source = {"attention_bias": False, "decoder_sparse_step": 1,
              "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
              "intermediate_size": 6144, "max_position_embeddings": 262144,
              "max_window_layers": 48, "mlp_only_layers": [],
              "model_type": "KeyeVL2", "moe_intermediate_size": 768,
              "norm_topk_prob": True, "num_attention_heads": 32,
              "num_experts_per_tok": 8, "num_key_value_heads": 4,
              "rms_norm_eps": 1e-06,
              "rope_scaling": {"mrope_section": [16, 24, 24],
                               "rope_type": "default", "type": "default"},
              "rope_theta": 10000000,
              "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                            "q_chunk_size": 512, "topk": 2048},
              "sliding_window": None, "tie_word_embeddings": False,
              "use_sliding_window": False}
    for key, value in source.items():
        assert key in config and config[key] == value, key
    assert published == {"num_hidden_layers": 48, "num_experts": 128,
                         "num_local_experts": 128, "vocab_size": 151936}
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts"] == config["num_local_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key]
    assert "16 chips share each layer" in config["deployment"]


@pytest.mark.parametrize("key, value", [
    ("model_type", "qwen3_moe"), ("attention_bias", True),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("sliding_window", 4096), ("use_sliding_window", True),
    ("tie_word_embeddings", True), ("num_local_experts", 16),
    ("sa_config", {"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 2, "topk": 2048})])
def test_family_refuses_what_the_package_does_not_build(key, value):
    name = "indexer_num_kv_heads" if key == "sa_config" else key
    with pytest.raises(ValueError, match=name):
        keye_vl2.build({**_config(), key: value}, CELL)


def test_program_against_the_reference_on_seeded_weights():
    """The family's loss (``L_LM + L_I`` through the chunked loss) and its
    gradients against the reference's on the CPU at the rehearsal's sizes,
    the choice the reference's own ``top_k``."""
    config = {**_config(), **keye_vl2.REHEARSAL["config"]}
    job = keye_vl2.build(config, keye_vl2.REHEARSAL["traffic"])
    params, extra = job.init(jax.random.key(5))
    tokens = job.make_batch(jax.random.key(6), 2)
    (got, sown), grads = jax.value_and_grad(
        job.loss_and_sown, has_aux=True)(params, extra, tokens)
    (want, (ce, index_loss, routing)), want_grads = reference.loss_and_grad(
        params, tokens, config)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(index_loss) > 0 and float(ce) > float(index_loss)
    assert jnp.array_equal(sown["block_1"]["experts"], routing[0]["own"])
    _, _, (_, own) = reference.mixer(
        sown["block_0"]["dsa_input"], params["block_0"]["dsa"], config)
    assert jnp.array_equal(sown["block_0"]["dsa_choice"] != 0, own)
    assert int(jnp.sum(own[0, -1])) == 16
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-4, \
            jax.tree_util.keystr(path)


def test_reference_imports_nothing_of_the_package():
    with open(reference.__file__) as f:
        text = f.read()
    assert "import horovod_tpu" not in text and "from horovod_tpu" not in text
    assert "jax.lax.top_k" in text and "pallas" not in text.replace(
        "no kernel", "")


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as this family's would read: block
    0's MLP products are the mixer's ``dsa_index`` and ``dsa_core``, block
    1's its ``dsa_select`` and ``dsa_target``."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/up/", "/block_0/dsa/dsa_index/"),
             ("/block_0/mlp/down/", "/block_0/dsa/dsa_core/"),
             ("/block_1/mlp/up/", "/block_2/dsa/dsa_select/"),
             ("/block_1/mlp/down/", "/block_2/dsa/dsa_target/"))

    def rewrite(name):
        for old, new in swaps:
            name = name.replace(old, new)
        return name

    monkeypatch.setattr(regions, "name_stacks", lambda p: {
        k: rewrite(v) for k, v in names.items()})
    trace = xplane.load(str(path))
    by_scope = [sum(regions.region_ms(trace, names, scope=old)[r]
                    for r in ("forward", "recompute", "backward"))
                for old, _ in swaps]
    return trace, by_scope


def test_the_readers_read_their_scopes_or_nothing(renamed, monkeypatch):
    trace, (index, core, select, target) = renamed
    read = lambda name, run={}: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, run)
    assert min(index, core, select, target) > 0
    assert read("dsa_core_ms") == pytest.approx(core)
    assert read("dsa_select_ms") == pytest.approx(select)
    assert read("dsa_index_ms") == pytest.approx(index + target)
    assert read("dsa_ms") == pytest.approx(index + core + select + target)
    job = keye_vl2.build(_config(), CELL)
    run = {"facts": job.facts, "peak": flops.peaks("TPU v5 lite")}
    # every event under the scope, not the kernels alone: 94.2 ms of
    # required products over what the scope took
    assert read("dsa_core_roofline", run) == pytest.approx(
        100 * 94.226 / core, rel=1e-3)
    # the recording holds no Pallas call: no kernel under dsa_select
    assert read("dsa_select_roofline", run) is None
    device = trace.devices[0]
    lo, hi, steps = trace.window(device)
    call = ('%pick.1 = s8[2] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    span = (hi - lo) / 4
    device.ops.append(xplane.Op(call, lo, lo + span, "kernel"))
    named = regions.name_stacks("x")
    monkeypatch.setattr(regions, "name_stacks", lambda p: {
        **named, "pick.1": "jit(step)/jvp(keye_vl2)/block_0/dsa/dsa_select/"
                           "hvt_dsa_choice"})
    least = 1e3 * run["facts"]["dsa"]["select_bytes_per_step"] / run["peak"][
        "hbm_bytes_per_s"]
    assert read("dsa_select_roofline", run) == pytest.approx(
        100 * least / (span / steps / 1e6), rel=1e-6)
    assert read("dsa_core_roofline", {"facts": {}, "peak": run["peak"]}) \
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
         "keyevl2-s16384", "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is False           # a rehearsal never counts
    assert result["failed"] == 0 and result["attempted"] > 2
    assert not [l for l in lines if "FAILED" in l], lines
    for check in ("step_loss_vs_reference",
                  "grad_vs_reference_given_experts_and_keys",
                  "router_is_float32", "experts_agree_with_reference",
                  "disagreements_are_near_ties",
                  "first_mixer_rows_hold_min_t_plus_1_and_topk_keys",
                  "first_mixer_keys_agree_with_top_k",
                  "last_mixer_differing_keys_are_near_ties",
                  "last_mixer_output_vs_reference_given_choice",
                  "last_mixer_index_loss_vs_reference_given_choice"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    assert any("the reference's L_LM" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert not set(result["metrics"]) & {*READERS, "moe_ms", "lm_head_ms"}
