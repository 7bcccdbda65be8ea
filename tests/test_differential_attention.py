"""Differential attention in ``models/transformer.py``'s ``Attention`` (the
three kinds that build it: over every causal key, inside a window, and a
cross layer over another layer's keys and values), at small sizes on the
CPU: against the masked-softmax reference of
``chipbench/reference/phi4flash.py`` and equal to two plain attentions
combined by hand; the flash kernels' path (the interpreter here) against
the einsum path, forward and gradients; ``lambda_init`` by the layer's
number, the pair's norm and the window each held by a case a wrong program
fails; LayerNorm and the biases in the tree where the configuration asks
and absent where it does not; the leaves' partition specs; the counter's
two labels.

Suite clock (``PERF.md`` section 3's rule): 21 test-seconds, 37 CPU-seconds
(``os.times()`` around the file alone, PR 66)."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import phi4flash as reference
from horovod_tpu import metrics
from small_models import random_tree
from horovod_tpu.models import GPT, GPTConfig, transformer
from horovod_tpu.models.transformer import Attention, param_partition_spec

S, D_MODEL, HEADS, KV, E, WINDOW, DEPTH = 24, 32, 8, 4, 8, 5, 15
CFG = GPTConfig(vocab_size=64, n_layers=1, d_model=D_MODEL, n_heads=HEADS,
                n_kv_heads=KV, head_dim=E, d_ff=64, attn_window=WINDOW,
                attn_window_rotary=False, rotary=False,
                attn_differential=True, attn_bias=True, layer_norm=True,
                norm_eps=1e-5, dtype=jnp.float32, use_flash=False)
EPS = CFG.norm_eps


def _far(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


def _layer(kind, cfg=CFG, depth=DEPTH):
    return Attention(cfg, rotary=False, depth=depth,
                     window=cfg.attn_window if kind == "window" else 0,
                     shared=kind == "cross")


@functools.cache
def _case(kind):
    """``(layer, params, x, read)``, the leaves seeded random ones by the
    tree's shapes (the biases away from 0, the norm's weight from 1)."""
    x = jax.random.normal(jax.random.key(0), (2, S, D_MODEL))
    read = None
    if kind == "cross":
        read = tuple(jax.random.normal(jax.random.key(i), (2, S, KV, E))
                     for i in (1, 2))
    layer = _layer(kind)
    params = random_tree(jax.eval_shape(
        layer.init, jax.random.key(3), x, jnp.arange(S), read)["params"],
        4, 0.2)
    return layer, params, x, read


def _apply(layer, params, x, read):
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    return layer.apply({"params": params}, x, positions, read)


def _jitted(layer, params, x, read):
    """``_apply`` as one program at the highest precision."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda *a: _apply(layer, *a))(params, x, read)


def _reference(kind, params, x, read, depth=DEPTH, window=WINDOW):
    one = lambda x, kv: reference.differential_attention(
        x, params, depth, EPS, kv=kv,
        window=window if kind == "window" else None)
    if read is None:
        return jax.vmap(lambda x: one(x, None))(x)
    return jax.vmap(one)(x, read)


@pytest.mark.parametrize("kind", ["full", "window", "cross"])
def test_layer_is_the_reference_forward_and_backward(kind):
    layer, params, x, read = _case(kind)
    weight = jax.random.normal(jax.random.key(5), x.shape)
    mine = lambda p, x, r: jnp.sum(_apply(layer, p, x, r) * weight)
    plain = lambda p, x, r: jnp.sum(_reference(kind, p, x, r) * weight)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(mine, (0, 1, 2)))(params, x, read)
        want = jax.jit(jax.value_and_grad(plain, (0, 1, 2)))(params, x, read)
    assert abs(got[0] - want[0]) < 1e-4 * abs(want[0])
    # float32's own rounding; a key's bias moves every score of a row alike
    # and has no gradient but rounding, in both
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        name = jax.tree_util.keystr(path)
        if "['k']['bias']" in name:
            assert float(jnp.abs(a).max()) < 1e-5
        else:
            assert _far(a, b) < 1e-4, name


@pytest.mark.parametrize("kind", ["full", "window"])
def test_layer_is_two_plain_attentions_combined_by_hand(kind):
    """Pair ``j``: heads ``2 j`` and ``2 j + 1`` of q on heads ``2 g`` and
    ``2 g + 1`` of k, ``g = j // 2``, each one plain softmax attention on
    the pair's value ``[v_2g | v_2g+1]``, then ``RMSNorm(A1 - lambda A2) w
    (1 - lambda_init)``."""
    layer, params, x, _ = _case(kind)
    x = x[:1]

    def by_hand(params, x):
        proj = lambda name: (jnp.einsum("sd,dhe->she", x,
                                        params[name]["kernel"])
                             + params[name]["bias"])
        q, k, v = proj("q"), proj("k"), proj("v")
        at = jnp.arange(S)
        seen = at[None, :] <= at[:, None]
        if kind == "window":
            seen &= at[None, :] > at[:, None] - WINDOW
        attend = lambda q, k, v: jax.nn.softmax(jnp.where(
            seen, q @ k.T / math.sqrt(E), -jnp.inf), -1) @ v
        start = 0.8 - 0.6 * math.exp(-0.3 * DEPTH)
        lam = (jnp.exp(params["lambda_q1"] @ params["lambda_k1"])
               - jnp.exp(params["lambda_q2"] @ params["lambda_k2"]) + start)
        pairs = []
        for j in range(HEADS // 2):
            g = j // (HEADS // KV)
            both = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
            o = (attend(q[:, 2 * j], k[:, 2 * g], both)
                 - lam * attend(q[:, 2 * j + 1], k[:, 2 * g + 1], both))
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + EPS)
            pairs.append(o * params["subln"] * (1 - start))
        return (jnp.einsum("she,hed->sd", jnp.stack(pairs, 1).reshape(
            S, HEADS, E), params["o"]["kernel"]) + params["o"]["bias"])

    with jax.default_matmul_precision("highest"):
        want = jax.jit(by_hand)(params, x[0])
    got = _jitted(layer, params, x, None)[0]
    assert _far(got, want) < 1e-5


@pytest.mark.parametrize("wrong, least", [
    ("lambda_init_of_layer_1", 0.5),    # the scale 1 - lambda_init is 3 x
    ("no_norm", 0.3),
    ("no_window", 0.05),
    ("window_one_less", 0.01),
], ids=lambda w: str(w))
def test_a_wrong_program_is_far_from_the_reference(wrong, least,
                                                   monkeypatch):
    """The sound layer is at 1e-5 of the reference (above); each of these
    is ``least`` away or more."""
    layer, params, x, read = _case("window")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: _reference("window", *a))(params, x, read)
    if wrong == "lambda_init_of_layer_1":
        layer = _layer("window", depth=1)
    elif wrong == "no_norm":
        monkeypatch.setattr(jax.lax, "rsqrt", lambda t: jnp.ones_like(t))
    elif wrong == "no_window":
        layer = _layer("full")
    else:
        layer = _layer("window", dataclasses.replace(
            CFG, attn_window=WINDOW - 1))
    assert _far(_jitted(layer, params, x, read), want) > least


def test_lambda_init_goes_by_the_layers_number():
    assert transformer.differential_lambda_init(0) == pytest.approx(0.2)
    assert transformer.differential_lambda_init(15) == pytest.approx(
        0.8 - 0.6 * math.exp(-4.5))
    # a patterned model numbers its decoder layers from ``first_layer``, a
    # mixer and the feed-forward layer after it one layer
    cfg = dataclasses.replace(CFG, n_layers=4, layer_pattern="W-*-",
                              first_layer=15)
    depths = []
    real = transformer.differential_lambda_init
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(transformer, "differential_lambda_init",
                      lambda depth: depths.append(depth) or real(depth))
        jax.eval_shape(GPT(cfg).init, jax.random.key(0),
                       jnp.zeros((1, S), jnp.int32))
    assert depths == [15, 16]


@pytest.mark.parametrize("kind", ["window", "cross"])
def test_flash_path_is_the_einsum_path(kind):
    """The kernels (interpreted here) at 128 positions, 8 maps of 8 on 4
    key heads with values of 16, against the einsum path: the output and
    every gradient. 2e-2: a bf16 model's own rounding; the kernels are
    held to float32 by their own tests."""
    s = 128
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, attn_window=32)
    x = jax.random.normal(jax.random.key(0), (1, s, D_MODEL), jnp.bfloat16)
    read = None if kind != "cross" else tuple(
        jax.random.normal(jax.random.key(i), (1, s, KV, E), jnp.bfloat16)
        for i in (1, 2))
    layers = [_layer(kind, dataclasses.replace(cfg, use_flash=flash))
              for flash in (False, True)]
    params = random_tree(jax.eval_shape(
        layers[0].init, jax.random.key(3), x, jnp.arange(s), read)["params"],
        4, 0.2)
    weight = jax.random.normal(jax.random.key(5), x.shape)
    loss = lambda layer: lambda p, x: jnp.sum(
        _apply(layer, p, x, read).astype(jnp.float32) * weight)
    want, got = (jax.jit(jax.value_and_grad(loss(layer), (0, 1)))(params, x)
                 for layer in layers)
    assert abs(got[0] - want[0]) < 2e-2 * abs(want[0]) + 1.0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        if "['k']['bias']" not in jax.tree_util.keystr(path):
            assert _far(a, b) < 2e-2, jax.tree_util.keystr(path)


def _tree(**changes):
    cfg = dataclasses.replace(CFG, n_layers=4, layer_pattern="*-X-",
                              **changes)
    return jax.eval_shape(GPT(cfg).init, jax.random.key(0),
                          jnp.zeros((1, S), jnp.int32))["params"]


def test_norms_and_biases_are_in_the_tree_where_the_configuration_asks():
    asked = _tree()
    assert set(asked["block_0"]["norm"]) == {"scale", "bias"}
    assert set(asked["ln_f"]) == {"scale", "bias"}
    full, cross = asked["block_0"]["attn"], asked["block_2"]["cross"]
    assert set(full) == {"q", "k", "v", "o", "lambda_q1", "lambda_k1",
                         "lambda_q2", "lambda_k2", "subln"}
    # a cross layer projects a query and an output and nothing else
    assert set(cross) == set(full) - {"k", "v"}
    assert all(set(full[name]) == {"kernel", "bias"} for name in "qkvo")
    assert full["q"]["bias"].shape == (HEADS, E)
    assert full["subln"].shape == (2 * E,)
    assert full["lambda_q1"].shape == (E,)
    plain = _tree(attn_bias=False, layer_norm=False, attn_differential=False)
    assert set(plain["block_0"]["norm"]) == {"scale"}
    assert set(plain["ln_f"]) == {"scale"}
    assert set(plain["block_0"]["attn"]) == {"q", "k", "v", "o"}
    assert set(plain["block_2"]["cross"]) == {"q", "o"}
    assert all(set(plain["block_0"]["attn"][name]) == {"kernel"}
               for name in "qkvo")


def test_partition_specs_of_the_new_leaves():
    specs = param_partition_spec(_tree(), tp_size=2)
    full, cross = specs["block_0"]["attn"], specs["block_2"]["cross"]
    for tree in (full, cross):
        assert tree["q"] == {"kernel": P(None, "tp", None),
                             "bias": P("tp", None)}
        assert tree["o"] == {"kernel": P("tp", None, None), "bias": P()}
        assert all(tree[name] == P() for name in (
            "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln"))
    assert full["k"] == {"kernel": P(None, "tp", None), "bias": P("tp", None)}
    assert specs["block_0"]["norm"] == {"scale": P(), "bias": P()}
    # key-value heads a tensor-parallel axis does not divide replicate,
    # their biases with them
    odd = param_partition_spec(_tree(), tp_size=8)["block_0"]["attn"]
    assert odd["k"] == {"kernel": P(), "bias": P()}
    assert odd["q"]["bias"] == P("tp", None)


def test_the_counter_says_differential_and_shared():
    def counted(differential, shared, window=0):
        m = metrics.registry().get("hvt_attn_layers_traced_total")
        return m.labels(heads=str(HEADS), kv_heads=str(KV), head_dim=str(E),
                        core="einsum", window=str(window), rotary="none",
                        blocks="0", differential=str(differential),
                        shared=str(shared)).value if m else 0.0

    labels = [(1, 0), (1, 1), (0, 0), (0, 1)]
    before = [counted(*label) for label in labels]
    _tree()
    assert [counted(*label) for label in labels] == [
        before[0] + 1, before[1] + 1, before[2], before[3]]
    _tree(attn_differential=False)
    assert [counted(*label) for label in labels] == [
        before[0] + 1, before[1] + 1, before[2] + 1, before[3] + 1]


@pytest.mark.parametrize("changes, words", [
    (dict(n_heads=6, n_kv_heads=3, head_dim=8), "both even"),
    (dict(heads_held=(0, 4)), "no heads_held"),
    (dict(diffusion_block=4), "diffusion"),
])
def test_what_differential_attention_does_not_build_is_refused(changes,
                                                               words):
    with pytest.raises(ValueError, match=words):
        jax.eval_shape(GPT(dataclasses.replace(CFG, **changes)).init,
                       jax.random.key(0), jnp.zeros((1, 2 * S), jnp.int32))
