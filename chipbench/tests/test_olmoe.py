"""The ``olmoe`` family: its plain reference against a case worked by
hand, its FLOP and parameter counts against the reference's own
operations and the package's tree, the grouped products' roofline
arithmetic, the comparison of two choices of experts, and the cell's
rehearsal."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops
from chipbench.families import olmoe
from chipbench.layer_metrics import moe_experts_roofline
from chipbench.reference import olmoe as reference
from chipbench.setup_sources import CHECKOUT


def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_reference_expert_layer_by_hand():
    """Two experts, one choice a token, in numpy: each token goes to its
    more probable expert and comes back times that probability; the two
    losses from their definitions."""
    rng = np.random.RandomState(0)
    h = rng.randn(6, 4).astype(np.float32)
    p = {"router": rng.randn(4, 2).astype(np.float32),
         "gate": rng.randn(2, 4, 3).astype(np.float32),
         "up": rng.randn(2, 4, 3).astype(np.float32),
         "down": rng.randn(2, 3, 4).astype(np.float32)}
    logits = h @ p["router"]
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = np.zeros_like(h)
    for t in range(6):
        e = int(np.argmax(probs[t]))
        hidden = _silu(h[t] @ p["gate"][e]) * (h[t] @ p["up"][e])
        want[t] = probs[t, e] * (hidden @ p["down"][e])
    share = np.bincount(np.argmax(probs, -1), minlength=2) / 6.0
    out, load_balance, router_z, routing = reference.experts_layer(
        jnp.asarray(h), p, 1)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)
    assert float(load_balance) == pytest.approx(
        2 * float(np.sum(share * probs.mean(0))), rel=1e-5)
    assert float(router_z) == pytest.approx(float(np.mean(
        np.log(np.exp(logits).sum(-1)) ** 2)), rel=1e-5)
    np.testing.assert_array_equal(np.asarray(routing["own"])[:, 0],
                                  np.argmax(probs, -1))
    # forced: the other expert, at this reference's own probability of it
    other = 1 - np.argmax(probs, -1)
    forced, _, _, routing = reference.experts_layer(
        jnp.asarray(h), p, 1, jnp.asarray(other)[:, None])
    for t in range(6):
        e = int(other[t])
        hidden = _silu(h[t] @ p["gate"][e]) * (h[t] @ p["up"][e])
        np.testing.assert_allclose(
            np.asarray(forced[t]), probs[t, e] * (hidden @ p["down"][e]),
            rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(routing["used"])[:, 0], other)


def test_flops_per_token_of_the_published_widths_by_hand():
    block = 4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert block == 67_239_936
    head = 50304 * 2048
    got = olmoe.train_flops_per_token(1, 2048, 64, 8, 1024, 50304, 4096)
    assert got == 6 * (block + head) + 6 * 2048 * 4096 == 1_071_906_816
    # at the source's depth the experts are three fifths of it, here 28%
    full = olmoe.train_flops_per_token(16, 2048, 64, 8, 1024, 50304, 4096)
    assert 6 * 16 * 8 * 3 * 2048 * 1024 / full == pytest.approx(0.613, abs=0.001)
    assert 6 * 8 * 3 * 2048 * 1024 / got == pytest.approx(0.282, abs=0.001)
    assert olmoe.n_params(1, 2048, 64, 1024, 50304) == 625_616_896


def test_flops_and_parameters_against_the_reference_and_the_tree():
    """One expert, one choice, one head, one sequence: every loop of the
    reference has one pass, so ``flops.forward_macs`` counts all of it.
    The reference multiplies the whole score matrix (the count takes the
    causal half) and s - 1 positions by the head (the count takes s)."""
    config = {"vocab_size": 48, "num_hidden_layers": 2, "hidden_size": 16,
              "num_attention_heads": 1, "num_key_value_heads": 1,
              "num_experts": 1, "num_experts_per_tok": 1,
              "intermediate_size": 8, "rope_theta": 10000,
              "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
              "norm_topk_prob": False, "dtype": "float32", "remat": False,
              "use_flash": False, "router_aux_loss_coef": 0.01,
              "router_z_loss_coef": 0.001,
              "optimizer": {"name": "adamw", "learning_rate": 1e-3}}
    s, d, f, v, layers = 12, 16, 8, 48, 2
    job = olmoe.build(config, {"seq_len": s, "per_chip_batch": 1})
    params, _ = jax.eval_shape(job.init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) \
        == job.facts["n_params"] == olmoe.n_params(layers, d, 1, f, v)
    tokens = jax.ShapeDtypeStruct((1, s), jnp.int32)
    macs = flops.forward_macs(
        lambda p, t: reference.parts(p, t, config)[0], params, tokens)
    block = 4 * d * d + d * 1 + 1 * 3 * d * f
    assert macs == s * layers * block + layers * 2 * s * s * d + (s - 1) * v * d
    counted = olmoe.train_flops_per_token(layers, d, 1, 1, f, v, s)
    assert counted * s == 6 * (s * layers * block + layers * s * s * d
                               + s * v * d)
    assert job.flops_per_item == counted


def test_grouped_product_counts_by_hand():
    rows, experts, d, f = 65536, 64, 2048, 1024
    assert moe_experts_roofline.product_flops(rows, d, f) == 2 * rows * d * f
    # bf16: the rows in (or out) on the wide side, on the narrow side,
    # and one stack of expert matrices
    assert moe_experts_roofline.product_bytes(rows, experts, d, f, 2) == 2 * (
        rows * d + rows * f + experts * d * f)
    seconds, bound = flops.roofline_seconds(
        moe_experts_roofline.product_flops(rows, d, f),
        moe_experts_roofline.product_bytes(rows, experts, d, f, 2),
        flops.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert seconds == pytest.approx(274.9e9 / 197e12, rel=1e-3)


def test_roofline_counts_required_products_not_the_calls_that_ran(
        monkeypatch):
    """The least time comes from the family's facts: 12 products a
    layer under remat, 9 without. The trace gives the time spent and
    nothing else, so a program with fewer or more calls under the scope
    (gate and up fused, a product split) reads against the same work."""
    peak = flops.peaks("TPU v5 lite")
    moe = {"layers": 1, "rows": 65536, "experts": 64, "d_model": 2048,
           "d_expert": 1024, "itemsize": 2}
    one = 1e3 * 2 * 65536 * 2048 * 1024 / 197e12
    least, bound = moe_experts_roofline.least_ms(
        {"moe": moe, "remat": True}, peak)
    assert bound == "compute" and least == pytest.approx(12 * one)
    assert moe_experts_roofline.least_ms(
        {"moe": {**moe, "layers": 2}, "remat": False},
        peak)[0] == pytest.approx(18 * one)

    class NoCalls:      # a trace whose operations may not be looked at
        def __getattr__(self, name):
            raise AssertionError(f"the trace's {name} was read")

    monkeypatch.setattr(moe_experts_roofline.moe_ms, "under",
                        lambda trace, scopes: 38.0)
    run = {"facts": {"moe": moe, "remat": True}, "peak": peak}
    assert moe_experts_roofline.read(NoCalls(), run) == pytest.approx(
        100 * 12 * one / 38.0)
    monkeypatch.setattr(moe_experts_roofline.moe_ms, "under",
                        lambda trace, scopes: None)
    assert moe_experts_roofline.read(NoCalls(), run) is None
    assert moe_experts_roofline.read(NoCalls(), {**run, "facts": {}}) is None


@pytest.mark.parametrize("key, value", [("rope_theta", 500000),
                                        ("norm_topk_prob", True)])
def test_family_refuses_what_the_package_does_not_build(key, value):
    with open(os.path.join(CHECKOUT, "chipbench/configs/olmoe-1b-7b.json")) as f:
        config = json.load(f)
    with pytest.raises(ValueError, match=key):
        olmoe.build({**config, key: value},
                    {"seq_len": 64, "per_chip_batch": 1})


def test_router_distance_reads_the_routers_own_arithmetic():
    """Probabilities made in float32 of the sown input read about 0;
    made of bf16 operands they read the bf16 step."""
    h = jax.random.normal(jax.random.key(0), (64, 32)).astype(jnp.bfloat16)
    router = 0.5 * jax.random.normal(jax.random.key(1), (32, 8))
    exact = jax.nn.softmax(h.astype(jnp.float32) @ router, -1)
    routed = {"router_input": h, "router_probs": exact}
    assert olmoe.router_distance(routed, router, 2) < 1e-5
    coarse = jax.nn.softmax(
        (h @ router.astype(jnp.bfloat16)).astype(jnp.float32), -1)
    routed = {"router_input": h, "router_probs": coarse}
    assert 1e-3 < olmoe.router_distance(routed, router, 2) < 1e-1


def test_choices_are_compared_by_share_and_by_gap():
    probs = np.array([[0.5, 0.3, 0.15, 0.05],
                      [0.4, 0.3, 0.29, 0.01]], np.float32)
    want = np.array([[0, 1], [0, 1]])
    assert olmoe.compare_choices(want[:, ::-1], probs, want) == (1.0, 0.0)
    # the second token's second choice swapped for its near-tie
    agree, gap = olmoe.compare_choices(np.array([[0, 1], [0, 2]]), probs,
                                       want)
    assert agree == 0.75 and gap == pytest.approx(0.01, rel=1e-5)
    # a choice that is no tie
    _, gap = olmoe.compare_choices(np.array([[0, 3], [0, 1]]), probs, want)
    assert gap == pytest.approx(0.25, rel=1e-5)
    assert olmoe.load(np.array([[0, 1], [0, 2]]), 4) == 2.0


def test_rehearsal_of_the_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "olmoe-s4096",
         "--seed", "2147483999", "--seconds", "1", "--trace", "1",
         "--rehearse"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is False           # a rehearsal never counts
    assert result["failed"] == 0 and result["attempted"] > 2
    assert not [l for l in lines if "FAILED" in l], lines
    for check in ("step_loss_vs_reference", "grad_vs_reference_given_experts",
                  "router_is_float32", "experts_agree_with_reference",
                  "disagreements_are_near_ties"):
        assert any(f"check {check}" in l and ": ok" in l for l in lines), check
    assert any("largest group over the mean" in l for l in lines)
    # traced, off the chip: no device plane, so only what needs no trace
    assert set(result["metrics"]) <= {"compile_s", "hbm_reserved"}
