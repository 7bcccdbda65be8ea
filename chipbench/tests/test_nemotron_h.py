"""The ``nemotron_h`` family: its FLOP and parameter counts against a hand
count, the package's tree and the reference's own operations; its
configuration against the catalog's rules; its three readers on a
recorded trace whose names are rewritten; and the cell's rehearsal."""

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
from chipbench.families import nemotron_h
from chipbench.reference import nemotron_h as reference
from chipbench.setup_sources import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "rehearsal_three_steps.xplane.pb.gz")


def _config():
    with open(os.path.join(
            CHECKOUT, "chipbench/configs/nemotron3-super-120b.json")) as f:
        return json.load(f)


CELL = {"seq_len": 8192, "per_chip_batch": 2}


def test_flops_per_token_of_the_cut_by_hand():
    """One chip's share at the published widths, a layer of each kind by
    hand; five of each and the head are the cell's 2.57 GFLOP a token."""
    mamba = (4096 * (2 * 1024 + 2 * 128 + 16) + 4 * (1024 + 256)
             + 2 * 16 * 64 * 128 + 1024 * 4096)
    assert mamba == 9_502_720 + 5_120 + 262_144 + 4_194_304
    attention = 2 * 4096 * 512 + 2 * 4096 * 128 + 4 * 128 * 8192
    assert attention == 4_194_304 + 1_048_576 + 4_194_304
    experts = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
               + 22 * 8 / 512 * 2 * 1024 * 2688)
    assert experts == 2_097_152 + 8_388_608 + 44_040_192 + 1_892_352
    head = 16384 * 4096
    job = nemotron_h.build(_config(), CELL)
    macs = job.facts["forward_macs_per_token"]
    assert macs == {"M": 5 * mamba, "*": attention, "E": 5 * experts,
                    "head": head}
    total = 5 * mamba + attention + 5 * experts + head
    assert job.flops_per_item == 6.0 * total == 2_570_754_048.0
    assert job.items_per_step_per_chip == 16384
    # the shares of the arithmetic the issue reckoned with
    assert 5 * 2 * 4096 * 5376 / total == pytest.approx(0.514, abs=0.001)
    assert 5 * mamba / total == pytest.approx(0.163, abs=0.001)
    assert job.facts["moe"] == {"layers": 5, "row_bound": 131072,
                                "rows_expected": 5632.0, "experts": 512,
                                "held": 8}
    assert job.facts["ssm"] == {"layers": 5, "heads": 16, "chunk": 256}


def test_parameters_of_the_cut_are_the_trees():
    """700.9 M parameters, 10.44 GiB at 16 bytes each: the count from
    shapes is the tree ``models.GPT`` builds, layer by layer in the
    pattern's order, and the router's bias is outside it."""
    job = nemotron_h.build(_config(), CELL)
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    counted = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert counted == job.facts["n_params"] == 700_862_960
    assert 16 * counted / 2 ** 30 == pytest.approx(10.44, abs=0.01)
    kinds = ["attn" if "attn" in params[f"block_{i}"] else
             "moe" if "moe" in params[f"block_{i}"] else "ssm"
             for i in range(11)]
    assert kinds == ["attn"] + ["moe", "ssm"] * 5
    assert params["block_1"]["moe"]["router"].shape == (4096, 512)
    assert params["block_1"]["moe"]["up"].shape == (8, 1024, 2688)
    assert params["block_2"]["ssm"]["A_log"].shape == (16,)
    assert params["block_0"]["attn"]["q"]["kernel"].shape == (4096, 4, 128)
    assert params["block_0"]["attn"]["k"]["kernel"].shape == (4096, 1, 128)
    assert params["lm_head"].shape == (16384, 4096)
    assert sorted(extra["buffers"]) == [f"block_{i}" for i in (1, 3, 5, 7, 9)]
    assert job.probe.facts["pattern"] == nemotron_h.PROBE_PATTERN
    assert job.probe.facts["n_params"] == sum(
        leaf.size for leaf in jax.tree.leaves(
            jax.eval_shape(job.probe.init, jax.random.key(0))[0]))


def test_count_against_the_references_own_operations():
    """A tiny share where every loop of the reference has one pass (one
    head, one expert held and chosen by every token): ``flops.py`` counts
    the reference's products. The reference multiplies the whole score
    matrix (the count takes the causal half), s - 1 positions by the head
    (the count takes s); its convolution and its recurrence's update are
    no products at all and its read-out is one inside the loop over the
    positions, which ``flops.py`` counts once (the count takes all three
    from the shapes)."""
    s, d, v = 12, 16, 48
    config = {**_config(), **nemotron_h.REHEARSAL["config"],
              "vocab_size": v, "hidden_size": d, "head_dim": 16,
              "num_attention_heads": 1, "num_key_value_heads": 1,
              "mamba_num_heads": 1, "n_routed_experts": 1,
              "num_experts_per_tok": 1, "experts_held_first": 0,
              "published": {"num_hidden_layers": 3, "n_routed_experts": 1,
                            "mamba_num_heads": 1, "n_groups": 1,
                            "num_attention_heads": 1,
                            "num_key_value_heads": 1}}
    job = nemotron_h.build(config, {"seq_len": s, "per_chip_batch": 1})
    params, extra = jax.eval_shape(job.init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) \
        == job.facts["n_params"]
    tokens = jax.ShapeDtypeStruct((1, s), jnp.int32)
    macs = flops.forward_macs(
        lambda p, b, t: reference._loss(p, b, t, config, None)[0],
        params, extra["buffers"], tokens)
    counted = job.facts["forward_macs_per_token"]
    hd, n, latent, f, shared = 8, 16, 32, 32, 48
    recurrence_and_conv = 2 * hd * n + 4 * (hd + 2 * n)
    scores = 16 * s                     # one head of 16, the causal half
    assert macs == (s * (counted["M"] - recurrence_and_conv) + hd * n
                    + s * (counted["*"] - scores) + 2 * s * s * 16
                    + s * counted["E"] + (s - 1) * v * d)
    assert counted["E"] == d * 1 + 2 * d * latent + 2 * d * shared \
        + 2 * latent * f


def test_configuration_keeps_the_sources_values():
    """Every number of the catalog's entry under its own key but those in
    ``reduced``; no width among them; the floors: a whole period, 8
    routed experts, an eighth of the vocabulary."""
    config = _config()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "nemotron3-super-120b")
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"])
    published = config["published"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
        assert not key.endswith(("_dim", "_rank", "_size")) or key in (
            "vocab_size",), key
    # the source's values, from the catalog's entry
    source = {"hidden_size": 4096, "head_dim": 128, "mamba_head_dim": 64,
              "ssm_state_size": 128, "conv_kernel": 4, "expand": 2,
              "moe_intermediate_size": 2688, "moe_latent_size": 1024,
              "moe_shared_expert_intermediate_size": 5376,
              "num_experts_per_tok": 22, "routed_scaling_factor": 5,
              "n_shared_experts": 1, "norm_eps": 1e-5, "chunk_size": 128,
              "intermediate_size": 2688, "max_position_embeddings": 262144}
    for key, value in source.items():
        assert config[key] == value, key
    assert published == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "mamba_num_heads": 128, "n_groups": 8, "num_attention_heads": 32,
        "num_key_value_heads": 2, "vocab_size": 131072,
        "num_nextn_predict_layers": 1,
        "hybrid_override_pattern": published["hybrid_override_pattern"]}
    whole = published["hybrid_override_pattern"]
    assert len(whole) == 88 and whole[25:36] == config[
        "hybrid_override_pattern"] == "*EMEMEMEMEM"
    assert (whole.count("M"), whole.count("E"), whole.count("*")) == (
        40, 40, 8)
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    for key in ("deployment", "assumed", "departures"):
        assert config[key]


@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("norm_topk_prob", False), ("mlp_hidden_act", "silu"),
    ("num_nextn_predict_layers", 1), ("time_step_max", 0.2),
    ("head_dim", 64)])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        nemotron_h.build({**_config(), key: value}, CELL)


def test_residual_writers_are_rescaled_by_the_published_depth():
    config = {**_config(), **nemotron_h.REHEARSAL["config"]}
    job = nemotron_h.build(config, nemotron_h.REHEARSAL["traffic"])
    params, _ = jax.jit(job.init)(jax.random.key(0))
    plain = nemotron_h.GPT(nemotron_h._model_config(config, 64)).init(
        jax.random.key(0), jnp.zeros((1, 64), jnp.int32))["params"]
    scale = 1 / (2 * 6) ** 0.5
    for block, mixer, leaf in (("block_0", "attn", "o"),
                               ("block_1", "moe", "latent_out"),
                               ("block_1", "moe", "shared_down"),
                               ("block_2", "ssm", "out_proj")):
        got, was = params[block][mixer][leaf], plain[block][mixer][leaf]
        got, was = (t["kernel"] if isinstance(t, dict) else t
                    for t in (got, was))
        assert jnp.allclose(got, was * scale), leaf
    for got, was in ((params["block_1"]["moe"]["up"],
                      plain["block_1"]["moe"]["up"]),
                     (params["embedding"], plain["embedding"])):
        assert jnp.allclose(got, was, rtol=1e-6)


@pytest.fixture
def renamed(tmp_path, monkeypatch):
    """The recording of a dense model's rehearsal where a run would have
    left it, with its names rewritten as a hybrid's would read: block 0's
    MLP is a scan, block 1's an in-projection, block 2's a shared expert
    and block 2's attention a latent projection."""
    folder = tmp_path / "traces" / "a-cell" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        path.write_bytes(f.read())
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "a-cell", "--trace", "1", "--trace-dir",
        str(tmp_path / "traces")])
    names = regions.name_stacks(str(path))
    swaps = (("/block_0/mlp/", "/block_0/ssm/ssm_scan/"),
             ("/block_1/mlp/", "/block_1/ssm/ssm_in_proj/"),
             ("/block_2/mlp/", "/block_2/moe/moe_shared/"),
             ("/block_2/attn/", "/block_2/moe/moe_latent/"))

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


def test_the_three_readers_read_their_scopes_or_nothing(renamed,
                                                        monkeypatch):
    trace, by_scope = renamed
    read = lambda name: importlib.import_module(
        f"chipbench.layer_metrics.{name}").read(trace, {})
    assert all(ms > 0 for ms in by_scope.values())
    assert read("ssm_scan_ms") == pytest.approx(by_scope["/block_0/mlp/"])
    assert read("ssm_ms") == pytest.approx(
        by_scope["/block_0/mlp/"] + by_scope["/block_1/mlp/"])
    assert read("moe_shared_ms") == pytest.approx(
        by_scope["/block_2/mlp/"] + by_scope["/block_2/attn/"])
    # the parent's program has none of the scopes: left out, not 0, and
    # nothing raised; so too without a device plane
    monkeypatch.undo()
    for name in ("ssm_ms", "ssm_scan_ms", "moe_shared_ms"):
        module = importlib.import_module(f"chipbench.layer_metrics.{name}")
        assert module.read(None, {}) is None
    monkeypatch.setattr(regions, "name_stacks", lambda p: {"op": "jit(f)/x"})
    monkeypatch.setattr(regions, "trace_file", lambda *a: "somewhere")
    for name in ("ssm_ms", "ssm_scan_ms", "moe_shared_ms"):
        assert importlib.import_module(
            f"chipbench.layer_metrics.{name}").read(trace, {}) is None


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "nemotron3s-s8192", "--seed", "2147483999", "--seconds", "1",
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
                  "mamba2_mixer_vs_position_by_position"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("rows of the experts held" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert set(result["metrics"]) <= {"compile_s", "hbm_reserved"}
