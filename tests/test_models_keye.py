"""The sparse-attention mixer (``models/dsa.py``, pattern letter ``S``) on
``models.GPT``'s normal path against the plain reference
(``chipbench/reference/keye_vl2.py``) on seeded weights, at a size where
most queries choose: forward, both losses, gradients, which leaves each
loss reaches, the kernels' path against the plain one, what ``remat`` keeps
of the indexer (its loss and its gradients are made once a layer), the
share tied to the model, the leaf rule, and that no other configuration's
tree moves."""

import collections
import dataclasses
import functools

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
    params = jax.jit(GPT(CFG).init)(jax.random.key(0), tokens)["params"]
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

    (value, (ce, index_loss, sown)), got = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params)
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
    by_lm = jax.jit(jax.grad(lambda p: _losses(CFG, p, tokens)[0]))(params)
    by_index = jax.jit(jax.grad(lambda p: _losses(CFG, p, tokens)[1]))(params)
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
    loss = lambda cfg: jax.jit(jax.value_and_grad(
        lambda p: sum(_losses(cfg, p, tokens)[:2])))(params)
    (want, want_grads), (got, got_grads) = loss(CFG), loss(kernels)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        assert _rel(g, w) < 5e-5, jax.tree_util.keystr(path)


# one mixer and one expert layer at half the length: the cases below trace
# the gradient's program eight times over
SMALL = dataclasses.replace(CFG, n_layers=2, layer_pattern="SE",
                            max_seq_len=SEQ // 2)


@functools.cache
def _small_model():
    tokens = jax.random.randint(jax.random.key(2), (2, SEQ // 2), 0, 128)
    return jax.jit(GPT(SMALL).init)(jax.random.key(3), tokens)["params"], \
        tokens


def _small_grad(kernels, remat):
    """``jax.grad`` of ``L_LM + L_I`` of the small model by its tree."""
    _, tokens = _small_model()
    cfg = dataclasses.replace(SMALL, use_flash=kernels, remat=remat)
    return jax.grad(lambda p: sum(_losses(cfg, p, tokens)[:2]))


@functools.cache
def _small_gradient(kernels, remat):
    return jax.jit(_small_grad(kernels, remat))(_small_model()[0])


@functools.cache
def _small_reference():
    """The reference's gradient, on the model's own choice and experts."""
    params, tokens = _small_model()
    sown = jax.jit(lambda p: _losses(SMALL, p, tokens, sow=True)[2])(params)
    (_, _), want = reference.loss_and_grad(
        params, tokens, CONFIG, [sown["block_1"]["moe"]["experts"][0]],
        [sown["block_0"]["dsa"]["dsa_choice"][0] != 0])
    return want


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_gradients_are_the_references_with_remat_and_without(kernels, remat):
    """The indexer's gradients leave the first pass under a name that
    ``remat`` keeps: with ``remat`` as without, on either path, the whole
    tree's gradient is the reference's (the indexer's four leaves to the
    first test's tolerance), and with ``remat`` it is what the same path
    gives without."""
    want = _small_reference()
    got = _small_gradient(kernels, remat)
    without = _small_gradient(kernels, False)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w, same in zip(jax.tree_util.tree_leaves_with_path(got),
                                  jax.tree.leaves(want),
                                  jax.tree.leaves(without)):
        name = jax.tree_util.keystr(path)
        indexer = path[-1].key in INDEXER
        assert float(jnp.linalg.norm(w)) > 0, name
        assert _rel(g, w) < (2e-5 if indexer or not kernels else 5e-5), name
        assert _rel(g, same) < 1e-6, name


def _calls(jaxpr, counts=None):
    """Of a jaxpr and every jaxpr inside it: Pallas calls by name, the
    ``[b, s, s]`` row maxima (the plain indexer loss's ``log_softmax``; the
    attention's are ``[b, H, s, s]``), and the names applied."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        kind = eqn.primitive.name
        if kind == "pallas_call":
            counts[eqn.params["name"]] += 1
            continue
        if kind == "reduce_max" and eqn.invars[0].aval.ndim == 3 \
                and eqn.invars[0].aval.shape[1] == eqn.invars[0].aval.shape[2]:
            counts["row_max"] += 1
        if kind == "name":
            counts["named " + eqn.params["name"]] += 1
        if kind == "top_k":
            counts["top_k"] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _calls(inner, counts)
    return counts


@pytest.mark.parametrize("kernels", [False, True], ids=["plain", "kernels"])
def test_recomputed_layer_runs_no_indexer_and_no_indexer_loss(kernels):
    """The gradient's program of a ``remat`` model holds the indexer's loss
    **once a layer**, as without ``remat`` (the parent's held it twice: a
    ``custom_vjp``'s forward rule is what the recomputation runs), and so
    the index scores and the choice; the attention itself runs again."""
    with_remat, without = (_calls(jax.make_jaxpr(_small_grad(kernels, remat))(
        _small_model()[0]).jaxpr) for remat in (True, False))
    once = (("hvt_dsa_loss", "hvt_dsa_index", "hvt_dsa_choice",
             "hvt_flash_bwd") if kernels else ("row_max", "top_k"))
    for name in once:
        assert with_remat[name] == without[name] == (
            2 if name == "top_k" else 1), name    # (the router's beside it)
    if kernels:
        assert (with_remat["hvt_flash_fwd"], without["hvt_flash_fwd"]) == (
            2, 1)


def test_index_gradients_are_named_for_the_policy_and_counted():
    """``KEPT_INDEX_GRADS`` is on the four leaves' gradients of a traced
    layer, ``models.GPT``'s ``remat`` policy saves a value of that name,
    and ``hvt_dsa_index_grads_kept_total`` counts the layer once."""
    from horovod_tpu.models.dsa import KEPT_CHOICE, KEPT_INDEX_GRADS

    params, tokens = _small_model()
    family = metrics.counter("hvt_dsa_index_grads_kept_total")  # no label
    before = family.value
    jaxpr = jax.make_jaxpr(lambda p: _losses(SMALL, p, tokens)[1])(
        params).jaxpr
    assert family.value == before + 1
    counts = _calls(jaxpr)
    assert counts["named " + KEPT_INDEX_GRADS] == len(INDEXER)
    assert counts["named " + KEPT_CHOICE] == 1
    (block,) = [e for e in jaxpr.eqns if "policy" in e.params
                and "dsa_index" in str(e.params["jaxpr"])]
    named = [e for e in block.params["jaxpr"].eqns
             if e.primitive.name == "name"]
    for eqn in named:
        assert block.params["policy"](eqn.primitive, **eqn.params), eqn
    assert {e.params["name"] for e in named} == {KEPT_CHOICE,
                                                 KEPT_INDEX_GRADS}
    assert not block.params["policy"](named[0].primitive, name="another")


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
            jax.eval_shape(GPT(dataclasses.replace(CFG, **bad)).init,
                           jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="attention over chosen keys"):
        jax.eval_shape(GPT(dataclasses.replace(CFG, layer_pattern="SEQE")).init,
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
    params = jax.jit(GPT(cfg).init)(jax.random.key(0), tokens)["params"]
    _, aux = jax.jit(lambda p: GPT(cfg).apply(
        {"params": p}, tokens, return_aux=True))(params)
    assert set(aux) == {"dsa_index", "load_balance", "router_z"}
