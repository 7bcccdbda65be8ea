"""The sparse decoder (OLMoE's block) and the hybrid decoder (Nemotron-H's
layers) through ``models.GPT``, each against its plain reference."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import small_models as small
from horovod_tpu.models import GPT


def _shapes(model, seq=20):
    """What ``model.init`` returns, as shapes: for a test that reads the
    tree, the names or a lowered text and no value."""
    return jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((2, seq), jnp.int32))


def _hybrid_unbuilt(**changes):
    """The small hybrid for a test that lowers its step and runs nothing:
    the parameters as shapes, the buffers (closed over by the loss, so
    arrays) as zeros."""
    model = GPT(small.hybrid_config(**changes))
    variables = _shapes(model)
    buffers = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
                           variables.get("buffers", {}))
    return model, variables["params"], buffers, jnp.zeros((2, 20), jnp.int32)


@pytest.mark.parametrize("remat", [False, True])
def test_sparse_gpt_matches_reference(remat):
    """Two layers of 64 experts, 8 a token, q and k normalised over their
    whole width, an untied head: loss and the gradient of every leaf
    against chipbench/reference/olmoe.py, to float32's summation order;
    remat changes nothing."""
    from chipbench.reference import olmoe as reference

    model, params, tokens = small.sparse_model(remat)
    assert {"lm_head", "embedding"} <= set(params)
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "moe"}
    assert set(params["block_0"]["attn"]) == {"q", "k", "v", "o", "q_norm",
                                              "k_norm"}
    assert params["block_0"]["attn"]["q_norm"]["scale"].shape == (32,)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: small.sparse_loss(model, p, tokens)))(params)
    (want, _), want_grads = reference.loss_and_grad(params, tokens,
                                                    small.SPARSE)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, want_flat, strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 1e-5, (jax.tree_util.keystr(path), err)
    plain = GPT(small.sparse_config(not remat))
    assert float(jax.jit(lambda p: small.sparse_loss(plain, p, tokens))(
        params)) == pytest.approx(float(got), rel=1e-6)
    # the hidden states and the head a memory-bounded loss multiplies
    hidden, logits = jax.jit(lambda p: (
        model.apply({"params": p}, tokens, return_hidden=True),
        model.apply({"params": p}, tokens)))(params)
    np.testing.assert_allclose(
        np.asarray(hidden @ params["lm_head"].T), np.asarray(logits),
        rtol=1e-5, atol=1e-5)


def test_param_partition_spec_of_a_sparse_model():
    from horovod_tpu.models.transformer import param_partition_spec

    params = _shapes(GPT(small.sparse_config(False)), seq=16)["params"]
    specs = param_partition_spec(params, ep_axis="ep")
    moe = specs["block_1"]["moe"]
    assert moe["gate"] == moe["up"] == P("ep", None, "tp")
    assert moe["down"] == P("ep", "tp", None) and moe["router"] == P()
    assert specs["lm_head"] == specs["embedding"] == P("tp", None)
    assert specs["block_0"]["attn"]["q_norm"]["scale"] == P()
    assert specs["block_0"]["attn"]["q"]["kernel"] == P(None, "tp", None)
    # without an ep axis the expert axis is not sharded
    assert param_partition_spec(params)["block_0"]["moe"]["gate"] == P(
        None, None, "tp")


def test_sparse_gpt_is_the_parents():
    """What the olmoe-1b-7b cell builds: the parameter tree of the commit
    before the hybrid fields (three stacks over every expert, no buffer,
    no latent or shared leaf), both auxiliary losses, and a step that
    carries the four scopes it had and none of the new ones."""
    import re

    model = GPT(small.sparse_config(remat=True))
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = _shapes(model, seq=16)
    assert set(variables) == {"params"}
    params = variables["params"]
    assert {k: v.shape for k, v in params["block_1"]["moe"].items()} == {
        "router": (32, 64), "gate": (64, 32, 8), "up": (64, 32, 8),
        "down": (64, 8, 32)}
    assert set(params["block_1"]) == {"ln1", "attn", "ln2", "moe"}
    _, aux = jax.eval_shape(lambda p: model.apply(
        {"params": p}, tokens, return_aux=True), params)
    assert set(aux) == {"load_balance", "router_z"}
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: small.sparse_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)))
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine"):
        assert [n for n in names if f"/{scope}/" in n], scope
    for new in ("ssm_", "moe_latent", "moe_shared", "/norm/"):
        assert not [n for n in names if new in n], new


_HYBRID_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                  "ssm_out_proj", "moe_latent", "moe_shared")


@pytest.mark.parametrize("remat", [False, True])
def test_hybrid_gpt_matches_reference(remat):
    """One mixer a layer in the pattern's order, all three kinds, each a
    chip's share: the tree, the loss and the gradient of every leaf
    against chipbench/reference/nemotron_h.py, to float32's summation
    order; remat changes nothing; the logits are the reference's."""
    from chipbench.reference import nemotron_h as reference

    model, params, buffers, tokens = small.hybrid_model(remat)
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(5)]
    assert kinds == [{"attn"}, {"moe"}, {"ssm"}, {"moe"}, {"ssm"}]
    assert params["block_0"]["attn"]["q"]["kernel"].shape == (32, 2, 4)
    assert params["block_0"]["attn"]["k"]["kernel"].shape == (32, 1, 4)
    assert params["block_1"]["moe"]["up"].shape == (4, 16, 24)
    assert params["block_2"]["ssm"]["in_proj"].shape == (32, 2 * 16 + 16 + 4)
    assert set(buffers) == {"block_1", "block_3"}
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: small.hybrid_loss(model, p, buffers, tokens)))(params)
    (want, routing), want_grads = reference.loss_and_grad(
        params, buffers, tokens, small.HYBRID)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 2 and routing[0]["own"].shape == (40, 3)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, want_flat, strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 2e-5, (jax.tree_util.keystr(path), err)
    plain = GPT(small.hybrid_config(not remat))
    assert float(jax.jit(lambda p: small.hybrid_loss(
        plain, p, buffers, tokens))(params)) == pytest.approx(
            float(got), rel=1e-6)
    # no positional term in the attention: with the Mamba-2 layers' and
    # the causal mask's order taken away a permutation of the positions
    # permutes the logits
    attention_only, only_params, _, _ = small.hybrid_model(pattern="*")
    apply = jax.jit(
        lambda t: attention_only.apply({"params": only_params}, t))
    np.testing.assert_allclose(np.asarray(apply(tokens)[:, -1]),
                               np.asarray(apply(tokens.at[:, :-1].set(
                                   tokens[:, -2::-1]))[:, -1]),
                               rtol=1e-4, atol=1e-5)


def test_hybrid_gradient_program_names_its_scopes_and_scatters_no_row():
    """The seven scopes the benchmark's readers look for are in the
    lowered step of a hybrid, forward and backward, beside the expert
    layer's four; and the only scatters the layers put in the compiled
    gradient program are the grouped products' bookkeeping and a held
    expert layer's sum of a round's rows by token (the embedding's and
    this test's own loss's are outside the blocks)."""
    import re

    model, params, buffers, tokens = _hybrid_unbuilt(remat=True)
    grad = jax.jit(jax.grad(
        lambda p: small.hybrid_loss(model, p, buffers, tokens)))
    # the names as the compiled program carries them, where a device trace
    # reads them: a held expert layer's round is lowered once and called,
    # and its callers' names stand before its own only from HLO on
    compiled = grad.lower(params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', compiled))
    for scope in _HYBRID_SCOPES + ("moe_route", "moe_dispatch",
                                   "moe_experts", "moe_combine"):
        assert [n for n in names if f"/{scope}/" in n and "jvp(" in n
                and "transpose(" not in n], scope
        assert [n for n in names if f"/{scope}/" in n
                and "transpose(jvp(" in n], scope
    assert [n for n in names if "rematted_computation" in n
            and "/ssm_scan/" in n]
    for line in compiled.splitlines():
        if " scatter(" in line:
            name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert ("/jit(gmm)/" in name or "/jit(tgmm)/" in name
                    or "/moe_dispatch/" in name or "/moe_combine/" in name
                    or "/block_" not in name), name


@pytest.mark.parametrize("remat", [False, True])
def test_recomputed_block_does_not_choose_its_experts_again(remat):
    """What a held expert layer's router chose has no gradient, and
    ``models.GPT`` keeps it under ``remat`` (``moe.HELD_CHOICE``: a bit a
    token and expert, and the slots' order): the gradient program of a
    hybrid with two expert layers holds ``top_k`` twice and sorts the
    ``T x count`` slots twice, once a layer, with ``remat`` as without,
    where a recomputed block would choose and sort a second time (the
    scores it chose from are made again: their gradient needs them)."""
    import re

    model, params, buffers, tokens = _hybrid_unbuilt(remat=remat)
    text = jax.jit(jax.grad(lambda p: small.hybrid_loss(
        model, p, buffers, tokens))).lower(params).as_text()
    slots = tokens.size * 4
    assert len(re.findall(r"chlo\.top_k", text)) == 2
    # (one lowered ``argsort`` that both layers call)
    assert len(re.findall(
        rf"call @argsort\w*\(.*\(tensor<{slots}xi32>\)", text)) == 2


def _take_attention_heads(p, first, count, group):
    kv = slice(first // group, max(first // group + 1,
                                   (first + count) // group))
    return {"q": {"kernel": p["q"]["kernel"][:, first:first + count]},
            "k": {"kernel": p["k"]["kernel"][:, kv]},
            "v": {"kernel": p["v"]["kernel"][:, kv]},
            "o": {"kernel": p["o"]["kernel"][first:first + count]}}


@pytest.mark.parametrize("count", [2, 4, 8], ids=[
    "part-of-a-group", "a-whole-group", "every-head"])
def test_the_shares_of_the_attention_heads_add_up(count):
    """8 query heads over 2 key-value heads, divided ``8 / count`` ways:
    each chip builds its heads' slices of q and o and the key-value head
    they read, and the shares' outputs summed are the uncut reference's
    attention (the out-projection is linear)."""
    from chipbench.reference import nemotron_h as reference
    from horovod_tpu.models import GPTConfig
    from horovod_tpu.models.transformer import Attention

    base = GPTConfig(d_model=32, n_heads=8, n_kv_heads=2, rotary=False,
                     dtype=jnp.float32, use_flash=False)
    x = jax.random.normal(jax.random.key(0), (2, 12, 32))
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12))
    layer = lambda cfg: Attention(cfg, rotary=cfg.rotary)
    whole = jax.jit(layer(base).init)(jax.random.key(1), x, positions)[
        "params"]
    want = jax.lax.map(lambda one: reference.attention(one, whole), x)
    total = 0.0
    for first in range(0, 8, count):
        cfg = dataclasses.replace(base, heads_held=(first, count))
        mine = _take_attention_heads(whole, first, count, 4)
        shapes = jax.eval_shape(layer(cfg).init, jax.random.key(1), x,
                                positions)["params"]
        assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
            lambda a: a.shape, jax.tree.map(lambda a: a, dict(shapes)))
        total = total + jax.jit(layer(cfg).apply)(
            {"params": mine}, x, positions)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("changes, match", [
    ({"layer_pattern": "*EM"}, "names 3 layers"),
    ({"layer_pattern": "*EM?M"}, "layer_pattern holds"),
    ({"heads_held": (1, 2)}, "heads held"),
    ({"heads_held": (0, 3)}, "heads held"),
    ({"heads_held": (6, 4)}, "heads held"),
    ({"ssm_heads_held": (2, 4)}, "whole groups"),
    ({"experts_held": (14, 4)}, "experts held"),
])
def test_hybrid_config_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        _shapes(GPT(small.hybrid_config(**changes)))


def test_dense_mlp_layer_of_a_pattern_and_param_partition_spec():
    """"-" is the dense MLP (here relu2) behind its one norm; and every
    new leaf has its PartitionSpec: Mamba-2's per-head vectors and
    out-projection rows over tp, the expert stacks over ep, the rest
    replicated."""
    from horovod_tpu.models.transformer import param_partition_spec

    params = _shapes(GPT(small.hybrid_config(pattern="-EM*")))["params"]
    assert set(params["block_0"]) == {"norm", "mlp"}
    specs = param_partition_spec(params, ep_axis="ep")
    ssm, moe = specs["block_2"]["ssm"], specs["block_1"]["moe"]
    assert ssm["A_log"] == ssm["dt_bias"] == ssm["D_skip"] == P("tp")
    assert ssm["norm_scale"] == P("tp") and ssm["out_proj"] == P("tp", None)
    assert ssm["in_proj"] == ssm["conv_kernel"] == ssm["conv_bias"] == P()
    assert moe["up"] == P("ep", None, "tp") and moe["down"] == P(
        "ep", "tp", None)
    for name in ("router", "latent_in", "latent_out", "shared_up",
                 "shared_down"):
        assert moe[name] == P(), name
    assert specs["block_0"]["norm"]["scale"] == P()
    assert specs["block_3"]["attn"]["o"]["kernel"] == P("tp", None, None)


def test_ssm_and_held_counters_show_on_metrics():
    """Both trace-time counters are on ``/metrics`` once a hybrid has
    been traced: the state-space layers by heads, state and chunk, the
    expert layers with what they hold."""
    from horovod_tpu import metrics

    model, params, buffers, tokens = _hybrid_unbuilt()
    jax.jit(lambda p: small.hybrid_loss(model, p, buffers, tokens)).lower(
        params)
    text = metrics.prometheus_text()
    assert re.search(r'hvt_ssm_layers_traced_total\{[^}]*chunk="20"[^}]*\}',
                     text), text[-2000:]
    assert 'heads="4"' in text and 'state="8"' in text
    assert re.search(r'hvt_moe_layers_traced_total\{[^}]*held="4"[^}]*\}',
                     text)
