"""The sparse-attention mixer (``models/dsa.py``, pattern letter ``S``) on
``models.GPT``'s normal path against the plain reference
(``chipbench/reference/keye_vl2.py``) on seeded weights, at a size where
most queries choose: forward, both losses, gradients, which leaves each
loss reaches, the kernels' path against the plain one, the share tied to
the model, the leaf rule, and that no other configuration's tree moves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import keye_vl2 as reference
from horovod_tpu import metrics
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.transformer import param_partition_spec

SEQ, TOPK = 256, 32
CFG = GPTConfig(
    vocab_size=128, n_layers=4, layer_pattern="SESE", d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, rotary_base=1e7, max_seq_len=SEQ,
    dtype=jnp.float32, tie_embeddings=False, mlp_act="swiglu", n_experts=8,
    experts_per_token=2, moe_expert_ff=32, moe_renormalise=True,
    experts_held=(2, 4), dsa_index_heads=4, dsa_index_dim=16, dsa_topk=TOPK,
    remat=True)
CONFIG = {"rms_norm_eps": 1e-6, "rope_theta": 1e7, "sa_config": {"topk": TOPK},
          "num_experts_per_tok": 2, "norm_topk_prob": True,
          "experts_held_first": 2}
INDEXER = ("index_q", "index_k", "index_k_norm", "index_w")


@pytest.fixture(scope="module")
def model():
    tokens = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 128)
    params = GPT(CFG).init(jax.random.key(0), tokens)["params"]
    # norms and the LayerNorm's bias off their initial ones and zeros
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(jax.random.key(
            hash(jax.tree_util.keystr(path)) % 2 ** 31), a.shape)
        if a.ndim <= 2 and a.shape[-1] <= 64 else a, params)
    return params, tokens


def _losses(cfg, params, tokens, sow=False):
    (hidden, aux), sown = GPT(cfg).apply(
        {"params": params}, tokens, return_hidden=True, return_aux=True,
        mutable=["intermediates"] if sow else [])
    logits = jnp.einsum("bsd,vd->bsv", hidden[:, :-1], params["lm_head"])
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, tokens[:, 1:, None], -1)[..., 0])
    return ce, aux["dsa_index"], sown.get("intermediates")


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def test_forward_both_losses_and_gradients_are_the_references(model):
    params, tokens = model

    def loss(p):
        ce, index_loss, sown = _losses(CFG, p, tokens, sow=True)
        return ce + index_loss, (ce, index_loss, sown)

    (value, (ce, index_loss, sown)), got = jax.value_and_grad(
        loss, has_aux=True)(params)
    own, (own_ce, own_index, routing) = reference.loss(params, tokens, CONFIG)
    # the reference's own top_k and its own experts
    assert float(ce) == pytest.approx(float(own_ce), rel=2e-6)
    assert float(index_loss) == pytest.approx(float(own_index), rel=2e-5)
    assert float(value) == pytest.approx(own, rel=2e-6)
    assert float(index_loss) > 0.05             # two layers' KL, not a zero
    choices = [sown[f"block_{i}"]["dsa"]["dsa_choice"][0] for i in (0, 2)]
    experts = [sown[f"block_{i}"]["moe"]["experts"][0] for i in (1, 3)]
    for choice in choices:                      # most queries choose
        counts = np.asarray(choice).sum(-1)
        assert np.array_equal(counts, np.broadcast_to(
            np.minimum(np.arange(SEQ) + 1, TOPK), counts.shape))
    assert np.array_equal(np.asarray(experts[0]),
                          np.asarray(routing[0]["own"]))
    (_, _), want = reference.loss_and_grad(
        params, tokens, CONFIG, experts, [c != 0 for c in choices])
    flat = lambda tree: {jax.tree_util.keystr(k): v for k, v in
                         jax.tree_util.tree_leaves_with_path(tree)}
    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    for name in got:
        assert _rel(got[name], want[name]) < 2e-5, name
        assert float(jnp.linalg.norm(want[name])) > 0, name


def test_each_loss_reaches_its_own_leaves_and_no_other(model):
    """``L_I`` reaches the indexer's four leaves of its own layer and
    nothing else; the language-model loss everything but them."""
    params, tokens = model
    by_lm = jax.grad(lambda p: _losses(CFG, p, tokens)[0])(params)
    by_index = jax.grad(lambda p: _losses(CFG, p, tokens)[1])(params)
    for path, g in jax.tree_util.tree_leaves_with_path(by_lm):
        indexer = path[-1].key in INDEXER
        assert bool(jnp.any(g != 0)) != indexer, jax.tree_util.keystr(path)
    for path, g in jax.tree_util.tree_leaves_with_path(by_index):
        indexer = path[-1].key in INDEXER
        assert bool(jnp.any(g != 0)) == indexer, jax.tree_util.keystr(path)


def test_kernel_path_is_the_plain_path(model):
    """``use_flash=True`` takes the index-score, choice, flash-with-choice
    and indexer-loss kernels (the interpreter here); the plain path is what
    ``"auto"`` takes off a TPU."""
    params, tokens = model
    kernels = dataclasses.replace(CFG, use_flash=True)
    loss = lambda cfg: jax.value_and_grad(
        lambda p: sum(_losses(cfg, p, tokens)[:2]))(params)
    (want, want_grads), (got, got_grads) = loss(CFG), loss(kernels)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        assert _rel(g, w) < 5e-5, jax.tree_util.keystr(path)


def test_the_16_shares_of_the_expert_layer_add_up_to_the_uncut_reference():
    """The share tied to the model: an expert layer of 32 experts cut 16
    ways (2 held a chip; the router whole, 4 a token, renormalised): the
    outputs of all 16 shares add up to the uncut reference's."""
    from horovod_tpu.models.moe import MoEMlp

    key_x, key_p = jax.random.split(jax.random.key(3))
    x = jax.random.normal(key_x, (2, 64, 32), jnp.float32)
    whole = MoEMlp(32, 48, 4, dtype=jnp.float32, renormalise=True)
    p = whole.init(key_p, x)["params"]
    config = {"num_experts_per_tok": 4, "norm_topk_prob": True}
    want, _ = reference.experts_layer(x.reshape(-1, 32), p, config)
    total = 0.0
    for share in range(16):
        held = (2 * share, 2)
        cut = {name: a[held[0]:held[0] + 2] if name != "router" else a
               for name, a in p.items()}
        out, _ = MoEMlp(32, 48, 4, dtype=jnp.float32, renormalise=True,
                        held=held).apply({"params": cut}, x)
        part, _ = reference.experts_layer(
            x.reshape(-1, 32), cut, {**config, "experts_held_first": held[0]})
        np.testing.assert_allclose(out.reshape(-1, 32), part, atol=2e-6)
        total = total + out.reshape(-1, 32)
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_leaf_rule_shards_the_heads_and_keeps_the_indexer_whole(model):
    params, _ = model
    spec = param_partition_spec(params, tp_axis="tp")["block_0"]["dsa"]
    assert spec["q_proj"] == spec["k_proj"] == spec["v_proj"] == \
        P(None, "tp", None)
    assert spec["o_proj"] == P("tp", None, None)
    for name in INDEXER + ("q_norm", "k_norm"):
        assert spec[name] == P(), name


def test_a_traced_layer_is_counted_with_its_sizes(model):
    params, tokens = model
    family = metrics.registry().get("hvt_dsa_layers_traced_total")
    at = lambda: family.labels(heads="4", kv_heads="2", head_dim="16",
                               index_heads="4", index_dim="16",
                               topk=str(TOPK)).value
    before = at()
    jax.eval_shape(lambda p: _losses(CFG, p, tokens)[0], params)
    assert at() == before + 2                   # one a traced layer


def test_mixer_refuses_what_it_does_not_build():
    tokens = jnp.zeros((1, 64), jnp.int32)
    for bad in (dict(dsa_index_heads=0), dict(n_kv_heads=3),
                dict(dsa_topk=0), dict(dsa_index_dim=15)):
        with pytest.raises(ValueError, match="sparse attention needs"):
            GPT(dataclasses.replace(CFG, **bad)).init(jax.random.key(0),
                                                      tokens)
    with pytest.raises(ValueError, match="attention over chosen keys"):
        GPT(dataclasses.replace(CFG, layer_pattern="SEXE")).init(
            jax.random.key(0), tokens)


def test_the_new_fields_default_to_no_such_layer():
    """No existing configuration names them: their defaults build no
    parameter and change no tree."""
    cfg = GPTConfig()
    assert (cfg.dsa_index_heads, cfg.dsa_index_dim, cfg.dsa_topk) == (
        0, 64, 2048)
    tokens = jnp.zeros((1, 32), jnp.int32)
    small = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                      d_ff=64, max_seq_len=32)
    tree = jax.eval_shape(GPT(small).init, jax.random.key(0), tokens)
    assert "dsa" not in str(jax.tree_util.tree_structure(tree))


def test_aux_keeps_every_layers_names():
    """An expert layer's auxiliary losses beside the indexers': a pattern
    of both returns both, each summed over its own layers."""
    cfg = dataclasses.replace(CFG, experts_held=None)
    tokens = jax.random.randint(jax.random.key(1), (1, 64), 0, 128)
    params = GPT(cfg).init(jax.random.key(0), tokens)["params"]
    _, aux = GPT(cfg).apply({"params": params}, tokens, return_aux=True)
    assert set(aux) == {"dsa_index", "load_balance", "router_z"}
