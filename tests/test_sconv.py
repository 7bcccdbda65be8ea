"""The gated short-convolution mixer (``models/sconv.py``) and the model
it was written for (LFM2-MoE's layers: the mixer, grouped-query attention
with per-head norms, a SwiGLU dense MLP beside experts of their own width
behind a sigmoid router) against ``chipbench/reference/lfm2_moe.py``, which
walks the convolution position by position and shares no code with the
package; the shares of an expert layer against the whole; the new scopes
in this model's step and no other's. That the other models are as they
were whatever this kind's fields say is ``test_models_kinds.py``'s."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import small_models as others
from chipbench.reference import lfm2_moe as reference
from horovod_tpu.models import GPT, GPTConfig, sconv
from horovod_tpu.models.moe import MoEMlp
from horovod_tpu.ops import causal_conv as conv_op

_LFM2 = {"norm_eps": 1e-5, "conv_L_cache": 3,
         "rope_parameters": {"rope_theta": 1e6}, "num_experts_per_tok": 4,
         "norm_topk_prob": True, "routed_scaling_factor": 1.0,
         "use_expert_bias": True, "experts_held_first": 8}
_SCOPES = ("sconv_in_proj", "sconv_gate_conv", "sconv_out_proj", "dense_mlp")


def _mixer(seq, taps=3, d=16):
    layer = sconv.ShortConv(taps, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, seq, d), jnp.float32)
    # the projections off their 0.02, so that the gates matter
    params = jax.jit(lambda x: jax.tree.map(
        lambda w: w * 10.0 if w.ndim > 1 and w.shape[0] == d else w,
        layer.init(jax.random.key(0), x)["params"]))(x)
    return layer, params, x


def _reference_mixer(params, x, taps):
    return jax.vmap(lambda one: reference.short_conv(
        one, params, {"conv_L_cache": taps}))(x)


# lengths shorter than the taps, equal to them, odd, and multiples of the
# 8 rows and 128 lanes a TPU's tiles (and a later kernel's blocks) have
@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("seq", [1, 2, 3, 5, 16, 37, 128, 136])
def test_mixer_matches_the_position_by_position_reference(seq, taps):
    """Forward and the gradient of every leaf and of the input."""
    layer, params, x = _mixer(seq, taps)
    assert {k: v.shape for k, v in params.items()} == {
        "in_proj": (16, 3, 16), "conv_kernel": (taps, 16),
        "out_proj": (16, 16)}
    target = jax.random.normal(jax.random.key(2), x.shape)
    loss = lambda f: lambda p, x: jnp.sum(f(p, x) * target)
    got = lambda p, x: layer.apply({"params": p}, x)
    want = lambda p, x: _reference_mixer(p, x, taps)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(jax.jit(got)(params, x)),
                                   np.asarray(want(params, x)),
                                   rtol=2e-5, atol=2e-5)
        grads = jax.jit(jax.grad(loss(got), (0, 1)))(params, x)
        want_grads = jax.grad(loss(want), (0, 1))(params, x)
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 2e-5, (jax.tree_util.keystr(path), err)


def test_gated_conv_is_the_equation_and_causal():
    """``C * sum_j w_j (B u)_{t - taps + 1 + j}`` with zeros before the
    sequence, by loops over positions and taps; and no position reads a
    later one."""
    rng = np.random.default_rng(0)
    b, c, u = (rng.standard_normal((1, 7, 4)).astype(np.float32)
               for _ in range(3))
    weight = rng.standard_normal((3, 4)).astype(np.float32)
    want = np.zeros_like(u)
    for t in range(7):
        for j in range(3):
            if t - 2 + j >= 0:
                want[0, t] += weight[j] * b[0, t - 2 + j] * u[0, t - 2 + j]
    want *= c
    got = sconv.gated_conv(*map(jnp.asarray, (b, c, u, weight)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    later = jnp.asarray(u).at[0, 5:].set(9.0)
    np.testing.assert_array_equal(
        np.asarray(sconv.gated_conv(b, c, later, weight))[0, :5],
        np.asarray(got)[0, :5])


def test_gates_and_taps_are_float32_in_a_bf16_layer():
    """bf16 in and out, float32 between: the layer's result is the float32
    formula on the bf16 operands, rounded once."""
    keys = jax.random.split(jax.random.key(3), 4)
    b, c, u = (jax.random.normal(k, (2, 24, 8)).astype(jnp.bfloat16)
               for k in keys[:3])
    weight = jax.random.normal(keys[3], (3, 8))
    got = sconv.gated_conv(b, c, u, weight)
    assert got.dtype == jnp.bfloat16
    f32 = lambda t: t.astype(jnp.float32)
    want = sconv.gated_conv(f32(b), f32(c), f32(u), weight)
    np.testing.assert_array_equal(np.asarray(f32(got)),
                                  np.asarray(f32(want.astype(jnp.bfloat16))))


def test_plain_convolution_without_its_activation_and_with_it_as_before():
    """``activation=None`` is the sum of the taps alone; the default is
    ``silu`` of it, and every caller that names no activation lowers to
    the program it had."""
    x = jax.random.normal(jax.random.key(0), (2, 12, 8))
    weight = jax.random.normal(jax.random.key(1), (4, 8))
    bias = jax.random.normal(jax.random.key(2), (8,))
    bare = conv_op.causal_conv_plain(x, weight, bias, activation=None)
    np.testing.assert_allclose(
        np.asarray(jax.nn.silu(bare)),
        np.asarray(conv_op.causal_conv_plain(x, weight, bias)), rtol=1e-6)
    as_before = jax.jit(lambda x, w, b: conv_op.causal_conv_plain(
        x, w, b)).lower(x, weight, bias).as_text()
    named = jax.jit(lambda x, w, b: conv_op.causal_conv_plain(
        x, w, b, activation=jax.nn.silu)).lower(x, weight, bias).as_text()
    assert as_before == named and "@silu" in as_before
    assert "silu" not in jax.jit(lambda x, w: conv_op.causal_conv_plain(
        x, w, activation=None)).lower(x, weight).as_text()


# ---- the whole model against its plain reference

@functools.cache
def _lfm2_model(remat=False, pattern="C-*ECE", **changes):
    """A share of a small LFM2-MoE: 4 query heads on 2 key-value heads of
    8, a SwiGLU MLP of 48 beside experts of 12, experts 8 to 15 of 64."""
    cfg = GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, n_kv_heads=2, head_norm=True, rotary_base=1e6,
        d_ff=48, mlp_act="swiglu", moe_expert_ff=12, dtype=jnp.float32,
        remat=remat, use_flash=False, norm_eps=1e-5, n_experts=64,
        experts_per_token=4, moe_score="sigmoid", experts_held=(8, 8))
    cfg = dataclasses.replace(cfg, **changes)
    model = GPT(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)

    @jax.jit
    def init(key):
        variables = model.init(key, tokens)
        # off their initial values: at 0.02 the layers barely move the
        # loss, and a norm's weight of 1 hides whether it is applied
        leaves, tree = jax.tree.flatten(variables["params"])
        keys = jax.random.split(jax.random.key(2), len(leaves))
        return jax.tree.unflatten(tree, [
            w + 0.2 * jax.random.normal(k, w.shape)
            for w, k in zip(leaves, keys)]), variables.get("buffers", {})

    return model, *init(jax.random.key(0)), tokens


def _lfm2_loss(model, params, buffers, tokens, sow=False):
    import optax

    logits, sown = model.apply({"params": params, "buffers": buffers},
                               tokens, mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


@pytest.mark.parametrize("remat", [False, True])
def test_lfm2_moe_gpt_matches_reference(remat):
    """All four kinds of layer in the source's order, the experts a chip's
    share, the embedding tied: the tree, the loss and the gradient of
    every leaf against the reference given the program's choice of
    experts, to float32's summation order; remat changes nothing."""
    model, params, buffers, tokens = _lfm2_model(remat)
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(6)]
    assert kinds == [{"sconv"}, {"mlp"}, {"attn"}, {"moe"}, {"sconv"},
                     {"moe"}]
    assert "lm_head" not in params
    assert {k: v["kernel"].shape for k, v in params["block_1"]["mlp"].items()
            } == {"gate": (32, 48), "up": (32, 48), "down": (48, 32)}
    experts = params["block_3"]["moe"]
    assert experts["router"].shape == (32, 64)
    assert experts["up"].shape == experts["gate"].shape == (8, 32, 12)
    assert experts["down"].shape == (8, 12, 32)
    attn = params["block_2"]["attn"]
    assert attn["q"]["kernel"].shape == (32, 4, 8)
    assert attn["k"]["kernel"].shape == (32, 2, 8)
    assert attn["q_norm"]["scale"].shape == (8,)
    assert set(buffers) == {"block_3", "block_5"}
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: _lfm2_loss(model, p, buffers, tokens, sow=True),
        has_aux=True))(params)
    chosen = [sown[f"block_{i}"]["moe"]["experts"][0] for i in (3, 5)]
    (want, routing), want_grads = reference.loss_and_grad(
        params, buffers, tokens, _LFM2, chosen)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 2 and routing[0]["own"].shape == (40, 4)
    for mine, theirs in zip(chosen, routing):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 2e-5, (jax.tree_util.keystr(path), err)
    plain = GPT(dataclasses.replace(model.cfg, remat=not remat))
    assert float(jax.jit(lambda p: _lfm2_loss(plain, p, buffers, tokens))(
        params)) == pytest.approx(float(got), rel=1e-6)


def test_the_choice_bias_enters_the_choice_alone():
    """``use_expert_bias``: a bias moves which experts a token gets, in
    program and reference alike, and not the weights of those both
    choose; no gradient reaches it."""
    model, params, buffers, tokens = _lfm2_model(pattern="CE")
    bias = jnp.zeros(64).at[8:12].set(0.3)
    moved = {"block_1": {"moe": {"choice_bias": bias}}}
    loss = jax.jit(lambda b: _lfm2_loss(model, params, b, tokens, sow=True))
    (base, sown), (with_bias, sown_moved) = loss(buffers), loss(moved)
    assert float(base) != float(with_bias)
    chosen = sown_moved["block_1"]["moe"]["experts"][0]
    assert not np.array_equal(np.asarray(chosen),
                              np.asarray(sown["block_1"]["moe"]["experts"][0]))
    want, routing = reference.loss(params, moved, tokens, _LFM2)
    np.testing.assert_array_equal(
        np.sort(np.asarray(chosen), -1),
        np.sort(np.asarray(routing[0]["own"]), -1))
    assert float(with_bias) == pytest.approx(want, rel=1e-5)
    grad = jax.jit(jax.grad(
        lambda b: _lfm2_loss(model, params, b, tokens)))(moved)
    assert float(jnp.abs(grad["block_1"]["moe"]["choice_bias"]).max()) == 0.0


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """The deployment in small: 64 experts divided 8 ways. Every share
    routes over all 64 and renormalises over all 4 a token chose; the
    shares' outputs (nothing here is computed alike on every chip: no
    shared expert, no latent) sum to the uncut reference's layer."""
    d, width, tokens = 32, 12, 48
    whole = MoEMlp(64, width, 4, dtype=jnp.float32, score="sigmoid")
    h = jax.random.normal(jax.random.key(1), (1, tokens, d))
    variables = jax.jit(whole.init)(jax.random.key(0), h)
    params = jax.tree.map(lambda w: w * 10.0, variables["params"])
    config = {**_LFM2, "experts_held_first": 0}
    with jax.default_matmul_precision("highest"):
        want, routing = reference.experts_layer(
            h[0], params, variables["buffers"]["choice_bias"], config)
        total, rows = jnp.zeros_like(want), 0
        for first in range(0, 64, 8):
            share = MoEMlp(64, width, 4, dtype=jnp.float32, score="sigmoid",
                           held=(first, 8))
            mine = {name: w if name == "router" else w[first:first + 8]
                    for name, w in params.items()}
            assert jax.tree.map(jnp.shape, jax.eval_shape(
                share.init, jax.random.key(0), h)["params"]) == jax.tree.map(
                    jnp.shape, mine)
            out, _ = jax.jit(share.apply)(
                {"params": mine, "buffers": variables["buffers"]}, h)
            total = total + out[0]
            rows += int(jnp.sum((routing["own"] >= first)
                                & (routing["own"] < first + 8)))
            # and one share alone is the reference's for the same share
            alone, _ = reference.experts_layer(
                h[0], mine, variables["buffers"]["choice_bias"],
                {**config, "experts_held_first": first})
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(alone),
                                       rtol=2e-5, atol=2e-6)
    assert rows == tokens * 4           # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    got, _ = jax.jit(whole.apply)({"params": params,
                                   "buffers": variables["buffers"]}, h)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# ---- scopes, counters, PartitionSpecs

def _names(loss, params) -> set:
    return set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(loss)).lower(
        params).as_text(debug_info=True)))


def test_lfm2_gradient_program_names_its_scopes():
    """The scopes the benchmark's readers look for are in the lowered
    step, forward and backward, and in the recomputed blocks; the mixers
    count themselves; and every new leaf has its PartitionSpec."""
    from horovod_tpu import metrics
    from horovod_tpu.models.transformer import param_partition_spec

    model, params, buffers, tokens = _lfm2_model(remat=True)
    names = _names(lambda p: _lfm2_loss(model, p, buffers, tokens), params)
    for scope in _SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        assert [n for n in found if "transpose" in n], scope
        assert [n for n in found if "transpose" not in n], scope
        # (the out-projection's result feeds no gradient but its own
        # operands', so a recomputed block leaves it out)
        assert bool([n for n in found if "rematted_computation" in n]) == (
            scope != "sconv_out_proj"), scope
    assert [n for n in names if "/moe_route/" in n]
    counted = metrics.registry().get("hvt_sconv_layers_traced_total")
    assert counted.labels(channels="32", taps="3").value >= 2
    assert re.search(
        r'hvt_sconv_layers_traced_total\{[^}]*channels="32"[^}]*\}',
        metrics.prometheus_text())
    assert re.search(r'hvt_moe_layers_traced_total\{[^}]*held="8"[^}]*\}',
                     metrics.prometheus_text())
    specs = param_partition_spec(params, ep_axis="ep")
    assert specs["block_0"]["sconv"] == {
        "in_proj": P(None, None, "tp"), "conv_kernel": P(None, "tp"),
        "out_proj": P("tp", None)}
    mlp = specs["block_1"]["mlp"]
    assert mlp["gate"]["kernel"] == mlp["up"]["kernel"] == P(None, "tp")
    assert mlp["down"]["kernel"] == P("tp", None)
    assert specs["block_3"]["moe"]["gate"] == P("ep", None, "tp")
    assert specs["block_3"]["moe"]["router"] == P()
    assert specs["embedding"] == P("tp", None)


_OTHERS = ("dense", "olmoe", "nemotron_h", "qwen3_next")


@functools.cache
def _other(name):
    """A small instance of a configuration the benchmark had before this
    mixer: ``(config, loss of the parameters given a model, parameters)``."""
    if name == "dense":
        cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                        d_ff=128, max_seq_len=8, dtype=jnp.bfloat16,
                        remat=True, use_flash="auto")
        tokens = jnp.zeros((2, 8), jnp.int32)
        params = jax.jit(GPT(cfg).init)(jax.random.key(0), tokens)["params"]
        return cfg, (lambda model: lambda p: model.apply(
            {"params": p}, tokens).astype(jnp.float32).sum()), params
    # the sparse and hybrid models by their shapes alone: nothing here
    # reads a value, and their builders initialise op by op
    make = {"olmoe": others.sparse_model, "nemotron_h": others.hybrid_model,
            "qwen3_next": others.qwen_model}[name]
    seen = []

    def shapes():
        model, *rest = make(remat=True)
        seen.append(model.cfg)
        return rest

    params, *given = jax.eval_shape(shapes)
    given = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), given)
    loss_of = {"olmoe": others.sparse_loss, "nemotron_h": others.hybrid_loss,
               "qwen3_next": others.qwen_loss}[name]
    return seen[0], (lambda model: lambda p: loss_of(model, p, *given)), params


@pytest.mark.parametrize("name", _OTHERS)
def test_no_other_models_step_holds_the_new_scopes(name):
    """``sconv_*`` is in no other configuration's lowered step; the dense
    MLP's scope only where the model has a dense MLP (the dense decoder:
    OLMoE's block and both hybrids' patterns have none)."""
    cfg, loss, params = _other(name)
    names = _names(loss(GPT(cfg)), params)
    assert not [n for n in names if "/sconv" in n]
    assert bool([n for n in names if "/dense_mlp/" in n]) == (name == "dense")
    leaves = [jax.tree_util.keystr(path) for path, _
              in jax.tree_util.tree_leaves_with_path(params)]
    assert not [leaf for leaf in leaves if "sconv" in leaf
                or "['mlp']['gate']" in leaf]


@pytest.mark.parametrize("field, value, changed", [
    ("moe_expert_ff", 20, {"gate": (8, 32, 20), "up": (8, 32, 20),
                           "down": (8, 20, 32)}),
    ("sconv_taps", 4, {"conv_kernel": (4, 32)}),
    ("d_ff", 40, {"mlp.gate": (32, 40), "mlp.up": (32, 40),
                  "mlp.down": (40, 32)}),
])
def test_lfm2_config_field_changes_its_part_only(field, value, changed):
    """The dense MLP's width and the experts' are two fields: each moves
    its own leaves and no other."""
    base, params, _, tokens = _lfm2_model()
    other = GPT(dataclasses.replace(base.cfg, **{field: value}))
    shapes = lambda tree: {
        ".".join(str(k.key) for k in path[1:] if k.key != "kernel"):
        leaf.shape for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if path[0].key in ("block_0", "block_1", "block_3")}
    before = shapes(params)
    after = shapes(jax.eval_shape(other.init, jax.random.key(0),
                                  tokens)["params"])
    moved = {name: shape for name, shape in after.items()
             if before[name] != shape}
    prefix = {"moe_expert_ff": "moe.", "sconv_taps": "sconv.", "d_ff": ""}
    assert moved == {prefix[field] + k: v for k, v in changed.items()}


def test_an_activation_the_mlp_does_not_have_is_refused():
    cfg = GPTConfig(vocab_size=16, n_layers=1, d_model=8, n_heads=2,
                    layer_pattern="-", mlp_act="geglu", dtype=jnp.float32)
    with pytest.raises(KeyError):
        GPT(cfg).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


def test_mixer_sows_its_input_and_output():
    layer, params, x = _mixer(12)
    out, sown = layer.apply({"params": params}, x, mutable=["intermediates"])
    kept = sown["intermediates"]
    np.testing.assert_array_equal(np.asarray(kept["sconv_input"][0]),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(kept["sconv_output"][0]),
                                  np.asarray(out))
