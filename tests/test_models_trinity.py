"""Trinity-Mini's layers in small (attention inside a window and turned by
the rotary three to one with attention over every causal key and not
turned, each gated and normed a head, a norm before and a norm after every
mixer, the embedding times sqrt(d), a SwiGLU dense MLP beside experts of
their own width behind a sigmoid router with a choice bias and one shared
expert, an untied head) through ``models.GPT`` against
``chipbench/reference/afmoe.py``, which shares no code with the package:
loss and gradients, each kind of mixer, the norm after the mixer and the
embedding's scale each against the reference and each absent from a model
that does not ask, the einsum path's mask, the 16 shares of an expert
layer against the whole, the new scopes and the counter's label, and what
is refused by name. The kernels alone are ``test_flash_window.py``'s."""

import dataclasses
import functools
import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import afmoe as reference
from horovod_tpu.models import GPT, GPTConfig
from horovod_tpu.models.moe import MoEMlp
from horovod_tpu.models.transformer import Attention

WINDOWED, FULL = "sliding_attention", "full_attention"
_WINDOW = 6
_TRINITY = {"rms_norm_eps": 1e-5, "rope_theta": 10000.0,
            "sliding_window": _WINDOW, "mup_enabled": True,
            "num_experts_per_tok": 8, "route_norm": True,
            "route_scale": 2.826, "experts_held_first": 8,
            "layer_types": [WINDOWED, FULL]}


def _config(remat=False, pattern="W-*E", **changes) -> GPTConfig:
    """A share of a small Trinity: 4 query heads on 2 key-value heads of
    8, a window of 6, experts 8 to 15 of 128 with 8 a token, a shared
    expert of 12, a dense MLP of 48, an untied head."""
    return GPTConfig(**{**dict(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, head_norm=True,
        attn_gate=True, rotary=False, attn_window=_WINDOW, post_norm=True,
        embed_scale=math.sqrt(32), d_ff=48, moe_expert_ff=12,
        dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-5, mlp_act="swiglu",
        n_experts=128, experts_per_token=8, moe_score="sigmoid",
        moe_renormalise=True, moe_route_scale=2.826, moe_shared_ff=12,
        experts_held=(8, 8)), **changes})


@functools.cache
def _state(pattern="W-*E"):
    """``(parameters, buffers, tokens)``: 20 positions, so that most
    queries lose keys to the window of 6."""
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)

    @jax.jit
    def init(key):
        variables = GPT(_config(pattern=pattern)).init(key, tokens)
        # at their 0.02 the experts and the router barely move the loss,
        # and the embedding is a tenth of what the normed mixers add;
        # norms' weights away from 1, so that each one's place shows
        def one(path, w):
            name = jax.tree_util.keystr(path)
            if ("moe" in name and w.ndim > 1) or "embedding" in name:
                return w * 10.0
            if "norm" in name:
                return w + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, zlib.crc32(name.encode())),
                    w.shape)
            return w

        return jax.tree_util.tree_map_with_path(
            one, variables["params"]), variables["buffers"]

    return *init(jax.random.key(0)), tokens


def _loss(model, params, buffers, tokens, sow=False):
    import optax

    logits, sown = model.apply({"params": params, "buffers": buffers},
                               tokens, mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


def test_trinity_gpt_matches_reference():
    """Both kinds of attention, the dense MLP and the expert layer, the
    experts a chip's share: the tree, the loss and the gradient of every
    leaf against the reference given the program's choice of experts,
    through the kernels (interpreted) under remat; and the einsum path's
    loss. The pattern is ``W-*E`` for the clock's sake: a windowed layer
    feeding an expert layer (the issue's ``W-WE*E``) is left to
    ``chipbench/tests/test_afmoe.py``, whose rehearsal runs the family's
    own check on ``W-WE*E``, and to the probe on the chip."""
    params, buffers, tokens = _state()
    model = GPT(_config(remat=True, use_flash=True))
    kinds = [set(params[f"block_{i}"]) - {"norm", "post_norm"}
             for i in range(4)]
    assert kinds == [{"attn"}, {"mlp"}, {"attn"}, {"moe"}]
    assert {k: jax.tree.leaves(v)[0].shape
            for k, v in params["block_0"]["attn"].items()} == {
        "q": (32, 4, 16), "k": (32, 2, 8), "v": (32, 2, 8), "o": (4, 8, 32),
        "q_norm": (8,), "k_norm": (8,)}
    assert all(params[f"block_{i}"]["post_norm"]["scale"].shape == (32,)
               for i in range(4))
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: _loss(model, p, buffers, tokens, sow=True),
        has_aux=True))(params)
    chosen = [sown["block_3"]["moe"]["experts"][0]]
    (want, routing), want_grads = reference.loss_and_grad(
        params, buffers, tokens, _TRINITY, chosen)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    plain = jax.jit(lambda p: _loss(GPT(_config()), p, buffers, tokens))
    np.testing.assert_allclose(float(plain(params)), float(want), rtol=1e-5)
    for mine, theirs in zip(chosen, routing, strict=True):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    for (path, g), (_, w) in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves_with_path(want_grads), strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 5e-5, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("block, windowed", [(0, True), (2, False)])
def test_each_kind_of_mixer_against_the_reference(block, windowed):
    """A mixer's sown input and output: the windowed one sees 6 keys and
    turns, the full one sees all and turns nothing; and each is not the
    other."""
    params, buffers, tokens = _state()
    _, sown = jax.jit(lambda p: _loss(GPT(_config()), p, buffers, tokens,
                                      sow=True))(params)
    mixer = sown[f"block_{block}"]["attn"]
    u, got = mixer["attn_input"][0], mixer["attn_output"][0]
    p = params[f"block_{block}"]["attn"]
    want = reference.mixer(u, p, _TRINITY, windowed)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    other = reference.mixer(u, p, _TRINITY, not windowed)
    assert float(jnp.linalg.norm(got - other) / jnp.linalg.norm(want)) > 0.05


def test_float32_parts_tell_a_softmax_in_bf16(monkeypatch):
    """The family's two measures of a mixer (``mixer_distances``) on a
    bf16 windowed mixer: the module built again with float32 products
    reads float32's own distance from the reference where the bf16 one
    reads its products'; with the scores rounded to bf16 and the softmax
    computed in bf16 (``benchmarks/trinity_wrong_programs.py``'s program)
    the second measure reads a hundred times what it read, which is the
    comparison that holds the softmax to float32, and a window one key
    short is far by both."""
    from benchmarks import trinity_wrong_programs as wrong
    from chipbench.families import afmoe as family
    from horovod_tpu.models import transformer

    cfg = _config(pattern="W", dtype=jnp.bfloat16)
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(2), (1, 20, 32), jnp.bfloat16)
    positions = jnp.arange(20)[None]
    layer = Attention(cfg, rotary=True, window=_WINDOW)
    p = layer.init(jax.random.key(0), u, positions)["params"]

    def distances(window=_WINDOW):
        mixer = lambda c: Attention(c, rotary=True, window=window)
        sown = {"attn_input": u, "attn_output": mixer(cfg).apply(
            {"params": p}, u, positions)}
        return family.mixer_distances(sown, p, _TRINITY, True, mixer(f32))

    sound = distances()
    assert sound["float32_parts"] < 1e-6 < 1e-3 < sound["mixer"] < 1e-2
    short = distances(_WINDOW - 1)
    assert min(short.values()) > 0.05
    monkeypatch.setattr(transformer, "_attend",
                        wrong._attend_with_a_bf16_softmax)
    coarse = distances()
    assert coarse["float32_parts"] > 100 * sound["float32_parts"]
    assert coarse["float32_parts"] > 5e-4


def test_the_einsum_path_masks_the_window():
    """``Attention`` alone, window 6 of 20 positions, no rotary: a change
    to a key seven or more positions back moves no output, one six back
    does."""
    cfg = _config(pattern="W")
    x = jax.random.normal(jax.random.key(2), (1, 20, 32))
    positions = jnp.arange(20)[None]
    layer = Attention(cfg, rotary=False, window=_WINDOW)
    variables = layer.init(jax.random.key(0), x, positions)
    out = lambda x: layer.apply(variables, x, positions)[0]
    moved = x.at[0, 4].add(1.0)
    changed = jnp.any(jnp.abs(out(moved) - out(x)) > 1e-6, axis=-1)
    assert changed.tolist() == [4 <= t < 4 + _WINDOW for t in range(20)]


@pytest.mark.parametrize("field, off, leaf", [
    ("post_norm", False, "post_norm"), ("embed_scale", 1.0, None)])
def test_each_new_piece_is_the_references_and_absent_where_not_asked(
        field, off, leaf):
    """With the piece the loss is the reference's; without it the loss is
    another, the tree has no such leaf and the lowered program no such
    scope or multiplication."""
    params, buffers, tokens = _state("WE")
    config = {**_TRINITY, "layer_types": [WINDOWED]}
    value = lambda cfg, p: float(jax.jit(
        lambda p: _loss(GPT(cfg), p, buffers, tokens))(p))
    want, _ = reference.loss(params, buffers, tokens, config)
    assert value(_config(pattern="WE"), params) == pytest.approx(
        want, rel=1e-5)
    without = _config(pattern="WE", **{field: off})
    bare = jax.eval_shape(GPT(without).init, jax.random.key(0),
                          tokens)["params"]
    mine = jax.tree.map(lambda _, p: p, bare, {
        k: ({n: w for n, w in v.items() if n in bare[k]}
            if isinstance(v, dict) else v) for k, v in params.items()})
    assert value(without, mine) != pytest.approx(want, rel=1e-4)
    names = lambda cfg, p: set(re.findall(r'loc\("([^"]*)"', jax.jit(
        lambda p: _loss(GPT(cfg), p, buffers, tokens)).lower(p).as_text(
            debug_info=True)))
    if leaf:
        assert leaf not in bare["block_0"] and leaf in params["block_0"]
        assert not [n for n in names(without, mine) if "/post_norm/" in n]
        assert [n for n in names(_config(pattern="WE"), params)
                if "/post_norm/" in n]
    else:
        scaled = [n for n in names(_config(pattern="WE"), params)
                  if n.endswith("/embed/mul")]
        assert scaled and not [n for n in names(without, mine)
                               if n.endswith("/embed/mul")]


def test_the_16_shares_of_an_expert_layer_add_up_to_the_whole():
    """The deployment in small: 128 experts divided 16 ways, 8 a token,
    scale 2.826. Every share routes over all 128 and renormalises over all
    8 a token chose; the shares' routed parts and the shared expert, which
    every chip computes alike, **counted once**, sum to the uncut
    reference's layer. One compiled share on a router whose columns are
    turned until that share's come first (the same layer with its experts
    renumbered)."""
    d, width, tokens = 32, 12, 48
    options = dict(dtype=jnp.float32, score="sigmoid", renormalise=True,
                   route_scale=2.826, shared_ff=12)
    whole = MoEMlp(128, width, 8, **options)
    h = jax.random.normal(jax.random.key(1), (1, tokens, d))
    variables = jax.jit(whole.init)(jax.random.key(0), h)
    params = jax.tree.map(lambda w: w * 10.0, variables["params"])
    bias = variables["buffers"]["choice_bias"]
    config = {**_TRINITY, "experts_held_first": 0}
    first_share = jax.jit(MoEMlp(128, width, 8, held=(0, 8),
                                 **options).apply)
    with jax.default_matmul_precision("highest"):
        want, routing = jax.jit(lambda h, p: reference.experts_layer(
            h, p, bias, config))(h[0], params)
        shared = (jax.nn.silu(h[0] @ params["shared_gate"])
                  * (h[0] @ params["shared_up"])) @ params["shared_down"]
        total, rows = jnp.zeros_like(want), 0
        for first in range(0, 128, 8):
            mine = {name: w[first:first + 8]
                    if name in ("gate", "up", "down") else w
                    for name, w in params.items()}
            out, _ = first_share(
                {"params": {**mine, "router": jnp.roll(
                    mine["router"], -first, axis=1)},
                 "buffers": {"choice_bias": jnp.roll(bias, -first)}}, h)
            total = total + (out[0] - shared)
            rows += int(jnp.sum((routing["own"] >= first)
                                & (routing["own"] < first + 8)))
    assert rows == tokens * 8           # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=2e-5, atol=3e-5)


def test_the_step_names_its_scopes_and_the_counter_the_window():
    """The windowed layers' products over positions are under
    ``attn_core/attn_window`` and the full layers' under ``attn_core``
    alone, forward, backward and recomputed; ``post_norm`` is in every
    layer; the windowed layers are turned and the full ones not; and
    ``hvt_attn_layers_traced_total`` counts the two kinds apart."""
    from horovod_tpu import metrics

    counted = lambda window: (
        lambda m: m.labels(heads="4", kv_heads="2", head_dim="8",
                           core="einsum", window=str(window),
                           rotary="plain" if window else "none",
                           blocks="0", differential="0",
                           shared="0").value
        if m else 0.0)(metrics.registry().get("hvt_attn_layers_traced_total"))
    params, buffers, tokens = _state()
    model = GPT(_config(remat=True))
    before = counted(_WINDOW), counted(0)
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: _loss(model, p, buffers, tokens))).lower(params).as_text(
            debug_info=True)))
    assert counted(_WINDOW) >= before[0] + 1 and counted(0) >= before[1] + 1
    assert counted(_WINDOW) - before[0] == counted(0) - before[1]
    for block, windowed in ((0, True), (2, False)):
        core = [n for n in names if f"/block_{block}/attn/attn_core/" in n]
        assert core and [n for n in core if "transpose" in n]
        assert [n for n in core if "rematted_computation" in n]
        assert all(("/attn_core/attn_window/" in n) == windowed for n in core)
        assert bool([n for n in names
                     if f"/block_{block}/attn/attn_rope/" in n]) == windowed
    for block in range(4):
        assert [n for n in names if f"/block_{block}/post_norm/" in n]
    assert re.search(r'hvt_attn_layers_traced_total\{[^}]*window="6"[^}]*\}',
                     metrics.prometheus_text())


@pytest.mark.parametrize("changes, match", [
    (dict(layer_pattern="W", n_layers=1, attn_window=0),
     "layer_pattern holds 'W' and attn_window is 0"),
    (dict(layer_pattern=None, n_layers=1), "post_norm without a "
     "layer_pattern is not built"),
    (dict(layer_pattern="Q", n_layers=1),
     r"'W' \(attention inside a window\)"),
])
def test_what_is_not_built_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        GPT(_config(**changes)).init(jax.random.key(0),
                                     jnp.zeros((1, 4), jnp.int32))


def test_a_window_on_the_ring_path_is_refused_by_name():
    from horovod_tpu.parallel.mesh import make_parallel_mesh

    cfg = _config(pattern="W", ring_mesh=make_parallel_mesh(sp=8))
    with pytest.raises(ValueError, match="a window .* on the ring path"):
        jax.eval_shape(GPT(cfg).init, jax.random.key(0),
                       jnp.zeros((1, 32), jnp.int32))
