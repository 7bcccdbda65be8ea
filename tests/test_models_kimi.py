"""Kimi Linear's layers in small (Kimi Delta Attention three to one with
latent attention that rotates nothing, a SwiGLU dense MLP beside experts of
their own width behind a sigmoid router with a choice bias and one shared
expert, an untied head) through ``models.GPT`` against
``chipbench/reference/kimi_linear.py``, which shares no code with the
package: loss and gradients, the 32 shares of an expert layer against the
whole, and the five new scopes in this model's step. The mixer and its rule
alone are ``test_kda.py``'s; that the other models are as they were
whatever this kind's fields say is ``test_models_kinds.py``'s."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import kimi_linear as reference
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.moe import MoEMlp

_KIMI = {"rms_norm_eps": 1e-5, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 4, "num_experts_per_token": 8,
         "moe_renormalize": True, "routed_scaling_factor": 2.446,
         "experts_held_first": 16,
         "linear_attn_config": {"num_heads": 4, "head_dim": 8}}
_SCOPES = ("kda_in_proj", "kda_conv", "kda_rule", "kda_gate_norm",
           "kda_out_proj")
# what reaches the decays sums exp(G_i - G_r) over every pair of positions
# where the reference multiplies one exp(g_t) after another
_DECAYS = ("A_log", "dt_bias", "decay_down", "decay_up")


def _kimi_config(remat=False, pattern="K-KELE") -> GPTConfig:
    """A share of a small Kimi Linear: delta-rule heads of 8 with gates of
    rank 8, latent-attention heads of 8 | 4 on values of 8 out of a latent
    of 16 and no rotary, experts 16 to 23 of 256 with 8 a token, a shared
    expert of 12, a dense MLP of 48, an untied head."""
    return GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, mla_kv_rank=16, mla_nope_dim=8,
        mla_rope_dim=4, mla_value_dim=8, rotary=False, kda_heads=4,
        kda_head_dim=8, kda_conv=4, kda_gate_rank=8, d_ff=48,
        moe_expert_ff=12, dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-5, mlp_act="swiglu",
        n_experts=256, experts_per_token=8, moe_score="sigmoid",
        moe_renormalise=True, moe_route_scale=2.446, moe_shared_ff=12,
        experts_held=(16, 8))


@functools.cache
def _kimi_state(pattern):
    """``(parameters, buffers, tokens)`` of the pattern's model: remat
    changes neither."""
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)

    @jax.jit
    def init(key):
        variables = GPT(_kimi_config(pattern=pattern)).init(key, tokens)
        # at their 0.02 the mixers, the experts and the router barely move
        # the loss
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w * 10.0 if any(
                name in str(path) for name in ("moe", "mla", "kda"))
            and w.ndim > 1 and "conv" not in str(path) else w,
            variables["params"]), variables.get("buffers", {})

    return *init(jax.random.key(0)), tokens


def _kimi_model(remat=False, pattern="K-KELE"):
    return GPT(_kimi_config(remat, pattern)), *_kimi_state(pattern)


def _kimi_loss(model, params, buffers, tokens, sow=False):
    import optax

    logits, sown = model.apply({"params": params, "buffers": buffers},
                               tokens, mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


@pytest.mark.parametrize("remat", [False, True])
def test_kimi_gpt_matches_reference(remat):
    """All four kinds of layer (a delta-rule mixer, the dense MLP, a
    latent attention, an expert layer), the experts a chip's share, the head untied: the tree, the loss and the gradient of
    every leaf against the reference given the program's choice of
    experts, to float32's summation order, with remat and without."""
    model, params, buffers, tokens = _kimi_model(remat, pattern="K-LE")
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(4)]
    assert kinds == [{"kda"}, {"mlp"}, {"mla"}, {"moe"}]
    assert {k: v.shape for k, v in params["block_0"]["kda"].items()} == {
        "in_proj_qkv": (32, 96), "conv_kernel": (4, 96),
        "in_proj_beta": (32, 4), "decay_down": (32, 8), "decay_up": (8, 32),
        "dt_bias": (32,), "A_log": (4,), "gate_down": (32, 8),
        "gate_up": (8, 32), "norm_scale": (8,), "out_proj": (32, 32)}
    experts = params["block_3"]["moe"]
    assert experts["router"].shape == (32, 256)
    assert experts["up"].shape == experts["gate"].shape == (8, 32, 12)
    assert experts["shared_up"].shape == (32, 12)
    assert "shared_expert_gate" not in experts
    assert params["lm_head"].shape == (64, 32)
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: _kimi_loss(model, p, buffers, tokens, sow=True),
        has_aux=True))(params)
    chosen = [sown["block_3"]["moe"]["experts"][0]]
    (want, routing), want_grads = reference.loss_and_grad(
        params, buffers, tokens, _KIMI, chosen)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 1 and routing[0]["own"].shape == (40, 8)
    for mine, theirs in zip(chosen, routing):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        name = jax.tree_util.keystr(path)
        assert err <= (4e-4 if any(d in name for d in _DECAYS) else 2e-5), (
            name, err)


def test_latent_attention_without_a_rotary_in_the_model():
    """``rotary`` false reaches ``LatentAttention``: the model's loss is
    the reference's, which turns nothing, and is not the rotated model's;
    the tree is the same either way."""
    model, params, buffers, tokens = _kimi_model(pattern="LE")
    got = float(jax.jit(lambda p: _kimi_loss(model, p, buffers, tokens))(
        params))
    want, _ = reference.loss(params, buffers, tokens, _KIMI)
    assert got == pytest.approx(want, rel=1e-5)
    turned = GPT(dataclasses.replace(model.cfg, rotary=True))
    assert float(jax.jit(lambda p: _kimi_loss(turned, p, buffers, tokens))(
        params)) != pytest.approx(want, rel=1e-4)
    shapes = lambda m: jax.tree.map(jnp.shape, jax.eval_shape(
        m.init, jax.random.key(0), tokens))
    assert shapes(turned) == shapes(model)


def test_the_32_shares_of_an_expert_layer_add_up_to_the_whole():
    """The deployment in small: 256 experts divided 32 ways, 8 a token,
    scale 2.446. Every share routes over all 256 and renormalises over all
    8 a token chose; the shares' routed parts and the shared expert, which
    every chip computes alike, **counted once**, sum to the uncut
    reference's layer. A share is a program of its own to the compiler
    (``held`` is static), so 29 of the 32 run as the first share's program
    on a router whose columns are turned until theirs come first, which is
    the same layer with its experts renumbered; the shares from 0, 8 and
    248 run as themselves, are the reference's for that share, and the
    two that also run turned give the same either way."""
    d, width, tokens = 32, 12, 48
    options = dict(dtype=jnp.float32, score="sigmoid", renormalise=True,
                   route_scale=2.446, shared_ff=12)
    whole = MoEMlp(256, width, 8, **options)
    h = jax.random.normal(jax.random.key(1), (1, tokens, d))
    variables = jax.jit(whole.init)(jax.random.key(0), h)
    params = jax.tree.map(lambda w: w * 10.0, variables["params"])
    bias = variables["buffers"]["choice_bias"]
    config = {**_KIMI, "experts_held_first": 0}
    stacks = ("gate", "up", "down")
    layer_of = lambda first: jax.jit(lambda h, p: reference.experts_layer(
        h, p, bias, {**config, "experts_held_first": first}))
    share_of = lambda first: jax.jit(MoEMlp(
        256, width, 8, held=(first, 8), **options).apply)
    first_share = share_of(0)
    with jax.default_matmul_precision("highest"):
        want, routing = layer_of(0)(h[0], params)
        shared = (jax.nn.silu(h[0] @ params["shared_gate"])
                  * (h[0] @ params["shared_up"])) @ params["shared_down"]
        total, rows = jnp.zeros_like(want), 0
        for first in range(0, 256, 8):
            mine = {name: w[first:first + 8] if name in stacks else w
                    for name, w in params.items()}
            out, _ = first_share(
                {"params": {**mine, "router": jnp.roll(mine["router"], -first,
                                                       axis=1)},
                 "buffers": {"choice_bias": jnp.roll(bias, -first)}}, h)
            total = total + (out[0] - shared)
            rows += int(jnp.sum((routing["own"] >= first)
                                & (routing["own"] < first + 8)))
            if first in (0, 8, 248):
                # as itself, and the reference's for that share alone
                itself, _ = share_of(first)(
                    {"params": mine, "buffers": variables["buffers"]}, h)
                np.testing.assert_allclose(np.asarray(out),
                                           np.asarray(itself), rtol=1e-6,
                                           atol=1e-6)
                alone, _ = layer_of(first)(h[0], mine)
                np.testing.assert_allclose(np.asarray(itself[0]),
                                           np.asarray(alone), rtol=2e-5,
                                           atol=2e-5)
    assert rows == tokens * 8           # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=2e-5, atol=3e-5)


# ---- scopes, counters, PartitionSpecs

def _names(loss, params) -> set:
    return set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(loss)).lower(
        params).as_text(debug_info=True)))


def test_kimi_gradient_program_names_its_scopes():
    """The five scopes the benchmark's readers look for are in the lowered
    step, forward and backward, and in the recomputed blocks; the latent
    attention's step 4 is in no part of it; the mixers count themselves;
    and every new leaf has its PartitionSpec."""
    from horovod_tpu import metrics
    from horovod_tpu.models.transformer import param_partition_spec

    model, params, buffers, tokens = _kimi_model(remat=True)
    names = _names(lambda p: _kimi_loss(model, p, buffers, tokens), params)
    for scope in _SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        assert [n for n in found if "transpose" in n], scope
        assert [n for n in found if "transpose" not in n], scope
        # (the out-projection's result feeds no gradient but its own
        # operands', so a recomputed block leaves it out)
        assert bool([n for n in found if "rematted_computation" in n]) == (
            scope != "kda_out_proj"), scope
    assert not [n for n in names if "/mla_rope/" in n]
    for scope in ("mla_core", "moe_route", "moe_shared", "dense_mlp"):
        assert [n for n in names if f"/{scope}/" in n], scope
    counted = metrics.registry().get("hvt_kda_layers_traced_total")
    assert counted.labels(heads="4", head_dim="8", gate_rank="8",
                          chunk="20").value >= 2
    assert re.search(
        r'hvt_kda_layers_traced_total\{[^}]*gate_rank="8"[^}]*\}',
        metrics.prometheus_text())
    specs = param_partition_spec(params, ep_axis="ep")
    assert specs["block_0"]["kda"]["out_proj"] == P("tp", None)
    assert specs["block_0"]["kda"]["decay_up"] == P(None, "tp")
    assert specs["block_0"]["kda"]["in_proj_qkv"] == P()
    assert specs["block_4"]["mla"]["o_proj"] == P("tp", None, None)
    assert specs["block_3"]["moe"]["gate"] == P("ep", None, "tp")


@pytest.mark.parametrize("field, value, changed", [
    ("kda_heads", 2, {"in_proj_qkv": (32, 48), "conv_kernel": (4, 48),
                      "in_proj_beta": (32, 2), "decay_up": (8, 16),
                      "dt_bias": (16,), "A_log": (2,), "gate_up": (8, 16),
                      "out_proj": (16, 32)}),
    ("kda_conv", 3, {"conv_kernel": (3, 96)}),
    ("kda_gate_rank", 4, {"decay_down": (32, 4), "decay_up": (4, 32),
                          "gate_down": (32, 4), "gate_up": (4, 32)}),
])
def test_each_size_moves_its_own_leaves(field, value, changed):
    base, params, _, tokens = _kimi_model(pattern="KE")
    other = GPT(dataclasses.replace(base.cfg, **{field: value}))
    after = jax.eval_shape(other.init, jax.random.key(0), tokens)["params"]
    shapes = lambda tree: {
        ".".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    before, after = shapes(params), shapes(after)
    assert {name: shape for name, shape in after.items()
            if before[name] != shape} == {
        f"block_0.kda.{k}": v for k, v in changed.items()}
