"""Kanana-2's layers in small (DeepSeek-V3's at sizes of its own: latent
attention, a SwiGLU dense MLP beside experts of their own width behind a
sigmoid router with a choice bias and two shared experts, an untied head)
through ``models.GPT`` against ``chipbench/reference/deepseek_v3.py``,
which shares no code with the package: loss and gradients, the choice
bias, the eight shares of an expert layer against the whole, the six new
scopes in this model's step. The mixer alone and the flash kernels at two
widths are ``test_mla.py``'s; that the other models are as they were
whatever this kind's fields say is ``test_models_kinds.py``'s."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import deepseek_v3 as reference
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.moe import MoEMlp

_KANANA = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "kv_lora_rank": 16,
           "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
           "num_experts_per_tok": 6, "norm_topk_prob": True,
           "routed_scaling_factor": 2.448, "experts_held_first": 16}
_SCOPES = ("mla_q_proj", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_core",
           "mla_out_proj")


def _kanana_config(remat=False, pattern="L-LELE") -> GPTConfig:
    """A share of a small Kanana-2: heads of 8 | 4 on values of 8 out of a
    latent of 16, experts 16 to 31 of 128 with 6 a token, a shared expert
    of 24, a dense MLP of 48, an untied head."""
    return GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, mla_kv_rank=16, mla_nope_dim=8,
        mla_rope_dim=4, mla_value_dim=8, rotary_base=1e6, d_ff=48,
        moe_expert_ff=12, dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-6, mlp_act="swiglu",
        n_experts=128, experts_per_token=6, moe_score="sigmoid",
        moe_renormalise=True, moe_route_scale=2.448, moe_shared_ff=24,
        experts_held=(16, 16))


@functools.cache
def _kanana_state(pattern):
    """``(parameters, buffers, tokens)`` of the pattern's model: remat
    changes neither."""
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)

    @jax.jit
    def init(key):
        variables = GPT(_kanana_config(pattern=pattern)).init(key, tokens)
        # at their 0.02 the mixers, the experts and the router barely move
        # the loss
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w * 10.0 if ("moe" in str(path)
                                         or "mla" in str(path))
            and w.ndim > 1 else w, variables["params"]), variables["buffers"]

    return *init(jax.random.key(0)), tokens


def _kanana_model(remat=False, pattern="L-LELE"):
    return GPT(_kanana_config(remat, pattern)), *_kanana_state(pattern)


def _kanana_loss(model, params, buffers, tokens, sow=False):
    import optax

    logits, sown = model.apply({"params": params, "buffers": buffers},
                               tokens, mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


_references = {}


def _reference_of(pattern, chosen):
    """The reference's loss, routing and gradients on the pattern's state
    given the program's choice of experts; made once for the choices a
    pattern's programs agree on (remat or not)."""
    params, buffers, tokens = _kanana_state(pattern)
    key = (pattern, tuple(np.asarray(c).tobytes() for c in chosen))
    if key not in _references:
        _references[key] = reference.loss_and_grad(
            params, buffers, tokens, _KANANA, list(chosen))
    return _references[key]


@pytest.mark.parametrize("remat", [False, True])
def test_kanana_gpt_matches_reference(remat):
    """All three kinds of layer in the source's order, the experts a
    chip's share, the head untied: the tree, the loss and the gradient of
    every leaf against the reference given the program's choice of
    experts, to float32's summation order, with remat and without."""
    model, params, buffers, tokens = _kanana_model(remat)
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(6)]
    assert kinds == [{"mla"}, {"mlp"}, {"mla"}, {"moe"}, {"mla"}, {"moe"}]
    assert {k: v.shape for k, v in params["block_0"]["mla"].items()} == {
        "q_proj": (32, 4, 12), "kv_down": (32, 20), "kv_norm": (16,),
        "kv_up": (16, 4, 16), "o_proj": (4, 8, 32)}
    experts = params["block_3"]["moe"]
    assert experts["router"].shape == (32, 128)
    assert experts["up"].shape == experts["gate"].shape == (16, 32, 12)
    assert experts["shared_up"].shape == (32, 24)
    assert "shared_expert_gate" not in experts
    assert params["lm_head"].shape == (64, 32)
    assert set(buffers) == {"block_3", "block_5"}
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: _kanana_loss(model, p, buffers, tokens, sow=True),
        has_aux=True))(params)
    chosen = [sown[f"block_{i}"]["moe"]["experts"][0] for i in (3, 5)]
    (want, routing), want_grads = _reference_of("L-LELE", tuple(chosen))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 2 and routing[0]["own"].shape == (40, 6)
    for mine, theirs in zip(chosen, routing):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 2e-5, (jax.tree_util.keystr(path), err)


def test_the_choice_bias_enters_the_choice_alone():
    """``noaux_tc``: a bias moves which experts a token gets, in program
    and reference alike, and not the weights of those both choose; no
    gradient reaches it."""
    model, params, buffers, tokens = _kanana_model(pattern="LE")
    bias = jnp.zeros(128).at[16:24].set(0.3)
    moved = {"block_1": {"moe": {"choice_bias": bias}}}
    loss = jax.jit(lambda b: _kanana_loss(model, params, b, tokens,
                                          sow=True))
    (base, sown), (with_bias, sown_moved) = loss(buffers), loss(moved)
    assert float(base) != float(with_bias)
    chosen = sown_moved["block_1"]["moe"]["experts"][0]
    assert not np.array_equal(np.asarray(chosen),
                              np.asarray(sown["block_1"]["moe"]["experts"][0]))
    want, routing = reference.loss(params, moved, tokens, _KANANA)
    np.testing.assert_array_equal(
        np.sort(np.asarray(chosen), -1),
        np.sort(np.asarray(routing[0]["own"]), -1))
    assert float(with_bias) == pytest.approx(want, rel=1e-5)
    grad = jax.jit(jax.grad(
        lambda b: _kanana_loss(model, params, b, tokens)))(moved)
    assert float(jnp.abs(grad["block_1"]["moe"]["choice_bias"]).max()) == 0.0


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """The deployment in small: 128 experts divided 8 ways, 6 a token,
    scale 2.448. Every share routes over all 128 and renormalises over all
    6 a token chose; the shares' routed parts and the shared expert, which
    every chip computes alike, **counted once**, sum to the uncut
    reference's layer."""
    d, width, tokens = 32, 12, 48
    options = dict(dtype=jnp.float32, score="sigmoid", renormalise=True,
                   route_scale=2.448, shared_ff=24)
    whole = MoEMlp(128, width, 6, **options)
    h = jax.random.normal(jax.random.key(1), (1, tokens, d))
    variables = jax.jit(whole.init)(jax.random.key(0), h)
    params = jax.tree.map(lambda w: w * 10.0, variables["params"])
    bias = variables["buffers"]["choice_bias"]
    config = {**_KANANA, "experts_held_first": 0}
    stacks = ("gate", "up", "down")
    layer_of = lambda first: jax.jit(lambda h, p: reference.experts_layer(
        h, p, bias, {**config, "experts_held_first": first}))
    with jax.default_matmul_precision("highest"):
        want, routing = layer_of(0)(h[0], params)
        shared = (jax.nn.silu(h[0] @ params["shared_gate"])
                  * (h[0] @ params["shared_up"])) @ params["shared_down"]
        total, rows = jnp.zeros_like(want), 0
        for first in range(0, 128, 16):
            share = MoEMlp(128, width, 6, held=(first, 16), **options)
            mine = {name: w[first:first + 16] if name in stacks else w
                    for name, w in params.items()}
            out, _ = jax.jit(share.apply)(
                {"params": mine, "buffers": variables["buffers"]}, h)
            total = total + (out[0] - shared)
            rows += int(jnp.sum((routing["own"] >= first)
                                & (routing["own"] < first + 16)))
            if first in (0, 112):
                # and one share alone is the reference's for that share
                alone, _ = layer_of(first)(h[0], mine)
                np.testing.assert_allclose(np.asarray(out[0]),
                                           np.asarray(alone), rtol=2e-5,
                                           atol=2e-5)
    assert rows == tokens * 6           # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got, _ = jax.jit(whole.apply)({"params": params,
                                   "buffers": variables["buffers"]}, h)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---- scopes, counters, PartitionSpecs

def _names(loss, params) -> set:
    return set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(loss)).lower(
        params).as_text(debug_info=True)))


def test_kanana_gradient_program_names_its_scopes():
    """The six scopes the benchmark's readers look for are in the lowered
    step, forward and backward, and in the recomputed blocks; the mixers
    count themselves; and every new leaf has its PartitionSpec."""
    from horovod_tpu import metrics
    from horovod_tpu.models.transformer import param_partition_spec

    model, params, buffers, tokens = _kanana_model(remat=True)
    names = _names(lambda p: _kanana_loss(model, p, buffers, tokens), params)
    for scope in _SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        assert [n for n in found if "transpose" in n], scope
        assert [n for n in found if "transpose" not in n], scope
        # (the out-projection's result feeds no gradient but its own
        # operands', so a recomputed block leaves it out)
        assert bool([n for n in found if "rematted_computation" in n]) == (
            scope != "mla_out_proj"), scope
    for scope in ("moe_route", "moe_shared", "dense_mlp"):
        assert [n for n in names if f"/{scope}/" in n], scope
    counted = metrics.registry().get("hvt_mla_layers_traced_total")
    assert counted.labels(heads="4", rank="16", nope="8", rope="4",
                          value="8").value >= 3
    assert re.search(
        r'hvt_mla_layers_traced_total\{[^}]*rank="16"[^}]*\}',
        metrics.prometheus_text())
    specs = param_partition_spec(params, ep_axis="ep")
    assert specs["block_0"]["mla"] == {
        "q_proj": P(None, "tp", None), "kv_down": P(), "kv_norm": P(),
        "kv_up": P(None, "tp", None), "o_proj": P("tp", None, None)}
    assert specs["block_3"]["moe"]["gate"] == P("ep", None, "tp")
    assert specs["block_1"]["mlp"]["down"]["kernel"] == P("tp", None)
    assert specs["lm_head"] == specs["embedding"] == P("tp", None)


@pytest.mark.parametrize("field, value, changed", [
    ("mla_kv_rank", 24, {"kv_down": (32, 28), "kv_norm": (24,),
                         "kv_up": (24, 4, 16)}),
    ("mla_nope_dim", 16, {"q_proj": (32, 4, 20), "kv_up": (16, 4, 24)}),
    ("mla_rope_dim", 8, {"q_proj": (32, 4, 16), "kv_down": (32, 24)}),
    ("mla_value_dim", 16, {"kv_up": (16, 4, 24), "o_proj": (4, 16, 32)}),
])
def test_each_width_moves_its_own_leaves(field, value, changed):
    base, params, _, tokens = _kanana_model(pattern="LE")
    other = GPT(dataclasses.replace(base.cfg, **{field: value}))
    after = jax.eval_shape(other.init, jax.random.key(0), tokens)["params"]
    shapes = lambda tree: {
        ".".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    before, after = shapes(params), shapes(after)
    assert {name: shape for name, shape in after.items()
            if before[name] != shape} == {
        f"block_0.mla.{k}": v for k, v in changed.items()}


def test_a_latent_layer_needs_its_rank():
    cfg = GPTConfig(vocab_size=16, n_layers=1, d_model=8, n_heads=2,
                    layer_pattern="L", dtype=jnp.float32)
    with pytest.raises(ValueError, match="latent rank"):
        GPT(cfg).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
