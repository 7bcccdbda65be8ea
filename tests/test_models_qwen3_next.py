"""Qwen3-Next's layers through ``models.GPT`` (Gated DeltaNet, gated
attention with per-head norms and a partial rotary, a renormalised softmax
router with a gated shared expert) against their plain reference."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import small_models as small
from horovod_tpu.models import GPT


_QWEN_SCOPES = ("gdn_in_proj", "gdn_conv", "gdn_rule", "gdn_gate_norm",
                "gdn_out_proj", "moe_shared")


@pytest.mark.parametrize("remat", [False, True])
def test_qwen3_next_gpt_matches_reference(remat):
    """All three kinds of layer in the source's order, the experts a
    chip's share: the tree, the loss and the gradient of every leaf
    against chipbench/reference/qwen3_next.py given the program's choice
    of experts, to float32's summation order; remat changes nothing."""
    from chipbench.reference import qwen3_next as reference

    model, params, tokens = small.qwen_model(remat)
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(6)]
    assert kinds == [{"gdn"}, {"moe"}, {"gdn"}, {"moe"}, {"attn"}, {"moe"}]
    attn, experts = params["block_4"]["attn"], params["block_1"]["moe"]
    assert attn["q"]["kernel"].shape == (32, 4, 32)     # [query | gate]
    assert attn["k"]["kernel"].shape == (32, 2, 16)
    assert attn["o"]["kernel"].shape == (4, 16, 32)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert experts["up"].shape == (4, 32, 16)
    assert experts["router"].shape == (32, 16)
    assert experts["shared_expert_gate"].shape == (32, 1)
    assert params["block_0"]["gdn"]["in_proj_qkvz"].shape == (32, 96)
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: small.qwen_loss(model, p, tokens, sow=True), has_aux=True))(
            params)
    chosen = [sown[f"block_{i}"]["moe"]["experts"][0] for i in (1, 3, 5)]
    (want, routing), want_grads = reference.loss_and_grad(
        params, tokens, small.QWEN, chosen)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 3 and routing[0]["own"].shape == (40, 3)
    for mine, theirs in zip(chosen, routing):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, want_flat, strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        # A_log and dt_bias: see tests/test_gdn.py's DECAY_REL
        bound = 2e-4 if path[-1].key in ("A_log", "dt_bias") else 2e-5
        assert err <= bound, (jax.tree_util.keystr(path), err)
    plain = GPT(small.qwen_config(not remat))
    assert float(jax.jit(lambda p: small.qwen_loss(plain, p, tokens))(
        params)) == pytest.approx(float(got), rel=1e-6)


def test_gated_attention_matches_the_formula():
    """The attention layer alone against the reference's: a head of 16
    where d_model / n_heads is 8, the (1 + w) norm a head on q and k, the
    rotary over the first quarter of a head at base 1e7, the output times
    sigmoid(gate); einsum path, float32."""
    from chipbench.reference import qwen3_next as reference
    from horovod_tpu.models.transformer import Attention

    cfg = small.qwen_config()
    layer = Attention(cfg, rotary=cfg.rotary)
    x = jax.random.normal(jax.random.key(3), (2, 24, 32))
    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))

    @jax.jit
    def init(x, positions):
        params = layer.init(jax.random.key(4), x, positions)["params"]
        keys = iter(jax.random.split(jax.random.key(5), 8))
        return jax.tree.map(
            lambda w: w + 0.3 * jax.random.normal(next(keys), w.shape),
            params)

    params = init(x, positions)
    got = jax.jit(lambda p: layer.apply({"params": p}, x, positions))(params)
    want = jax.vmap(lambda h: reference.attention(h, params, small.QWEN))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # positions past the rotated quarter carry no position: with q's and
    # k's first four channels zeroed the rotary changes nothing
    from horovod_tpu.ops.rotary import rotary

    t = jax.random.normal(jax.random.key(6), (2, 24, 4, 16))
    turned = rotary(t, positions, 1e7, 4)
    np.testing.assert_array_equal(np.asarray(turned[..., 4:]),
                                  np.asarray(t[..., 4:]))
    assert float(jnp.max(jnp.abs(turned[:, 1:, :, :4] - t[:, 1:, :, :4]))) > .1
    np.testing.assert_allclose(
        np.asarray(rotary(t, positions, 10000.0, None)),
        np.asarray(rotary(t, positions, 10000.0)), rtol=0, atol=0)


def test_unit_offset_norm_is_one_plus_its_weight():
    from horovod_tpu.models.transformer import RMSNorm

    x = jax.random.normal(jax.random.key(0), (3, 8))
    plain, offset = RMSNorm(1e-6), RMSNorm(1e-6, unit_offset=True)
    p_plain = plain.init(jax.random.key(1), x)["params"]
    p_offset = offset.init(jax.random.key(1), x)["params"]
    np.testing.assert_array_equal(np.asarray(p_plain["scale"]), np.ones(8))
    np.testing.assert_array_equal(np.asarray(p_offset["scale"]), np.zeros(8))
    np.testing.assert_allclose(
        np.asarray(offset.apply({"params": p_offset}, x)),
        np.asarray(plain.apply({"params": p_plain}, x)), rtol=1e-6)
    w = jax.random.normal(jax.random.key(2), (8,))
    np.testing.assert_allclose(
        np.asarray(offset.apply({"params": {"scale": w}}, x)),
        np.asarray(plain.apply({"params": {"scale": 1.0 + w}}, x)),
        rtol=1e-6)


@pytest.mark.parametrize("field, value, new_leaves", [
    ("head_dim", 32, set()),
    ("head_norm", True, {"q_norm", "k_norm"}),
    ("rotary_base", 1e7, set()),
    ("rotary_fraction", 0.25, set()),
    ("attn_gate", True, set()),
])
def test_qwen_attention_field_changes_its_part_only(field, value, new_leaves):
    """Each field Qwen3-Next's attention needed defaults to the layer as
    it was: the leaves it adds, and logits that differ from the default
    model's once the parameters are off their initial values."""
    from horovod_tpu.models import GPTConfig

    base = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                     d_ff=64, dtype=jnp.float32, use_flash=False)
    cfg = dataclasses.replace(base, **{field: value})
    tokens = jax.random.randint(jax.random.key(2), (1, 12), 0, 64)
    params = jax.jit(GPT(cfg).init)(jax.random.key(0), tokens)["params"]
    base_params = jax.jit(GPT(base).init)(
        jax.random.key(0), tokens)["params"]
    names = lambda tree: {str(getattr(k, "key", k)) for path, _ in
                          jax.tree_util.tree_leaves_with_path(tree)
                          for k in path}
    assert names(params) - names(base_params) == new_leaves
    got = jax.jit(GPT(cfg).apply)({"params": params}, tokens)
    want = jax.jit(GPT(base).apply)({"params": base_params}, tokens)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) > 1e-4


def test_qwen3_next_gradient_program_names_its_scopes():
    """The scopes the benchmark's readers look for are in the lowered
    step, forward and backward; the mixers count themselves; and every
    new leaf has its PartitionSpec."""
    from horovod_tpu import metrics
    from horovod_tpu.models.transformer import param_partition_spec

    # the lowered step and the specs read shapes and names alone
    model = GPT(small.qwen_config(remat=True))
    tokens = jnp.zeros((2, 20), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)["params"]
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: small.qwen_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)))
    for scope in _QWEN_SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        assert [n for n in found if "transpose" in n], scope
        assert [n for n in found if "transpose" not in n], scope
    assert [n for n in names if "/moe_route/" in n]
    counted = metrics.registry().get("hvt_gdn_layers_traced_total")
    assert counted.labels(value_heads="4", key_dim="8", value_dim="8",
                          chunk="20").value >= 2
    specs = param_partition_spec(params, ep_axis="ep")
    assert specs["block_0"]["gdn"] == {
        "in_proj_qkvz": P(), "in_proj_ba": P(), "conv_kernel": P(),
        "dt_bias": P("tp"), "A_log": P("tp"), "norm_scale": P(),
        "out_proj": P("tp", None)}
    attn = specs["block_4"]["attn"]
    assert attn["q"]["kernel"] == P(None, "tp", None)
    assert attn["o"]["kernel"] == P("tp", None, None)
    assert attn["q_norm"]["scale"] == P() and attn["k_norm"]["scale"] == P()
    assert specs["block_1"]["moe"]["shared_expert_gate"] == P()
    assert specs["block_1"]["moe"]["up"] == P("ep", None, "tp")


def test_both_norms_of_q_and_k_at_once_are_refused():
    from horovod_tpu.models import GPTConfig

    cfg = GPTConfig(vocab_size=16, n_layers=1, d_model=8, n_heads=2,
                    layer_pattern="*", qk_norm=True, head_norm=True,
                    dtype=jnp.float32)
    with pytest.raises(ValueError, match="one or the other"):
        GPT(cfg).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


@functools.cache
def _qwen_sound():
    """The small model with its loss and the reference's, made once."""
    from chipbench.reference import qwen3_next as reference

    model, params, tokens = small.qwen_model()
    loss = jax.jit(lambda p: small.qwen_loss(model, p, tokens))(params)
    return (model, params, tokens, float(loss),
            reference.loss(params, tokens, small.QWEN)[0])


# wrong programs: each reads a loss the family's step-loss comparison
# refuses (``LOSS_REL_BOUND``, the bound `chipbench/families/qwen3_next.py`
# holds the measured step to), where the program as it is passes it
@pytest.mark.parametrize("wrong, changes", [
    ("rotary-over-the-whole-head", {"rotary_fraction": 1.0}),
    ("attention-gate-left-out", {"attn_gate": False}),
    ("weights-not-renormalised", {"moe_renormalise": False}),
    ("shared-expert-gate-left-out", {"moe_shared_gate": False}),
    ("norm-without-its-one", {"norm_unit_offset": False}),
    ("norm-over-the-projected-width", {"head_norm": False, "qk_norm": True}),
])
def test_wrong_qwen3_next_models_are_refused(wrong, changes):
    from chipbench import compare
    from chipbench.families import qwen3_next as family

    model, params, tokens, loss, want = _qwen_sound()
    sound = compare.close("loss", loss, want, family.LOSS_REL_BOUND,
                          floor=1.0)
    assert sound.ok, sound.line()
    other = GPT(small.qwen_config(**changes))
    theirs = jax.jit(other.init)(jax.random.key(0), tokens)["params"]

    def fitted(path, leaf):
        """The right model's leaf, cut to the wrong one's shape (the q
        projection without its gate's columns, a norm over the width)."""
        mine = params
        for k in path:
            mine = mine.get(k.key) if isinstance(mine, dict) else None
            if mine is None:
                return leaf
        if mine.shape == leaf.shape:
            return mine
        if path[-2].key == "q":
            return mine[..., :leaf.shape[-1]]
        return jnp.resize(mine, leaf.shape)

    theirs = jax.tree_util.tree_map_with_path(fitted, theirs)
    far = compare.close(
        "loss", float(jax.jit(lambda p: small.qwen_loss(other, p, tokens))(
            theirs)), want, family.LOSS_REL_BOUND, floor=1.0)
    assert not far.ok, (wrong, far.line())
