"""The latent-attention mixer (``models/mla.py``) against
``chipbench/reference/deepseek_v3.py``'s attention, which rotates the
published interleaved pairs, walks the queries in blocks and shares no
code with the package; and the flash kernels at a value width apart from
the query-key width against the plain formula, with every one-width
caller's tiles and VMEM limits as they were. The model the mixer was
written for is ``test_models_kanana.py``'s (a file of its own, so that
``--dist loadfile`` gives the two to two workers)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v3 as reference
from horovod_tpu.models import mla
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.rotary import rotary

_KANANA = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "kv_lora_rank": 16,
           "qk_nope_head_dim": 8, "qk_rope_head_dim": 4}


def _mixer(seq, dtype=jnp.float32, use_flash=False, d=32, heads=4):
    layer = mla.LatentAttention(heads, 16, 8, 4, 8, rotary_base=1e6,
                                use_flash=use_flash, dtype=dtype)
    x = jax.random.normal(jax.random.key(1), (2, seq, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(seq), (2, seq))
    params = jax.jit(layer.init)(jax.random.key(0), x, positions)["params"]
    # off their 0.02 and the norm's weight off one, so that the scores are
    # no constant and the norm's weight matters
    keys = iter(jax.random.split(jax.random.key(2), len(params)))
    params = {name: (w * 10.0 if w.ndim > 1 else w + 0.3 * jax.random.normal(
        next(keys), w.shape)) for name, w in params.items()}
    return layer, params, x, positions


def _reference_mixer(params, x):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(lambda one, p: reference.latent_attention(
            one, p, _KANANA), in_axes=(0, None)))(x, params)


# lengths the reference's query block divides and does not, a multiple of
# the 8 rows and 128 lanes a TPU's tiles have, and the kernels interpreted
@pytest.mark.parametrize("seq, use_flash, dtype", [
    (5, False, jnp.float32), (32, False, jnp.float32),
    (40, False, jnp.float32), (128, True, jnp.float32),
    (40, False, jnp.bfloat16), (128, True, jnp.bfloat16)])
def test_mixer_matches_the_reference(seq, use_flash, dtype, monkeypatch):
    """The mixer against the reference's attention on its own input: the
    halves the program rotates (its weights' columns regrouped) against
    the published interleaved pairs, the shared rotated key, the latent's
    norm, the scale of the whole query-key width; in bf16 to bf16's
    rounding."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    layer, params, x, positions = _mixer(seq, dtype, use_flash)
    got = jax.jit(layer.apply)({"params": params}, x, positions)
    assert got.shape == x.shape and got.dtype == dtype
    want = _reference_mixer(params, x)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err <= (3e-2 if dtype == jnp.bfloat16 else 2e-5), err


@pytest.mark.parametrize("seq, dtype", [(128, jnp.float32),
                                        (256, jnp.float32),
                                        (128, jnp.bfloat16)])
def test_mixer_on_the_kernels_equals_its_einsum_path(seq, dtype):
    """``use_flash=True`` hands the kernels the four parts (interpreted
    here) and ``False`` assembles the query and the key whole for einsums:
    the output and the gradients of every parameter and of the input
    agree, the shared rotated key's among them (``kv_down``'s last
    columns), to float32's sums or to bf16's rounding."""
    x32 = None
    results = []
    for use_flash in (True, False):
        layer, params, x, positions = _mixer(seq, dtype, use_flash)
        x32 = x

        def loss(params, x):
            out = layer.apply({"params": params}, x, positions)
            return jnp.sum(out.astype(jnp.float32) * jnp.cos(
                jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape)))

        with jax.default_matmul_precision("highest"):
            results.append(jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
                params, x))
    tol = 4e-2 if dtype == jnp.bfloat16 else 2e-4
    (got, g_got), (want, g_want) = results
    assert abs(float(got) - float(want)) <= tol * max(abs(float(want)), 1.0)
    for a, w in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want),
                    strict=True):
        assert a.shape == w.shape and a.dtype == w.dtype
        err = float(jnp.linalg.norm(a - w) / jnp.linalg.norm(w))
        assert err <= tol, err
    assert x32.shape == (2, seq, 32)


def test_the_kernels_get_the_parts_and_never_a_whole_key(monkeypatch):
    """On the kernels' path neither ``whole_key`` nor a concatenation of
    the query runs: the entry is called with ``q_n``, ``k_n`` and ``v`` a
    head and the pair ``q_r [b, s, H, e]``, ``k_r [b, s, e]``; on the
    einsum path the key is assembled whole, once."""
    calls = []
    real = mla.flash.flash_attention
    monkeypatch.setattr(mla.flash, "flash_attention", lambda *a, **kw: (
        calls.append(([t.shape for t in a],
                      {k: v.shape if getattr(v, "ndim", 0) else v
                       for k, v in kw.items()})),
        real(*a, **kw))[1])
    keys = []
    whole = mla.whole_key
    monkeypatch.setattr(mla, "whole_key", lambda k_n, k_r: (
        keys.append(k_n.shape), whole(k_n, k_r))[1])
    layer, params, x, positions = _mixer(128, use_flash=True)
    calls.clear()                   # the initialisation's own trace
    jax.eval_shape(layer.apply, {"params": params}, x, positions)
    assert keys == [] and calls == [(
        [(2, 128, 4, 8), (2, 128, 4, 8), (2, 128, 4, 8)],
        {"q_r": (2, 128, 4, 4), "k_r": (2, 128, 4), "causal": True,
         "scale": mla.score_scale(8, 4)})]
    layer, params, x, positions = _mixer(128, use_flash=False)
    keys.clear()
    jax.eval_shape(layer.apply, {"params": params}, x, positions)
    assert keys == [(2, 128, 4, 8)] and len(calls) == 1


def test_parameters_are_the_published_ones_whatever_the_path():
    """Names, shapes, dtypes, initialisation and ``mla_leaf_spec`` of the
    mixer's parameters: ``q_proj [d, H, n + e]`` and ``kv_up [r, H, n +
    v]`` whole, a head's columns in the published order, so a checkpoint
    written before the kernels took the parts loads as it is."""
    trees = []
    for use_flash in (True, False):
        layer = mla.LatentAttention(4, 16, 8, 4, 6, rotary_base=1e6,
                                    use_flash=use_flash, dtype=jnp.bfloat16)
        x = jnp.zeros((2, 128, 32), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(128), (2, 128))
        trees.append(jax.jit(layer.init)(jax.random.key(0), x,
                                         positions)["params"])
    assert {name: (w.shape, w.dtype) for name, w in trees[0].items()} == {
        "q_proj": ((32, 4, 12), jnp.float32),
        "kv_down": ((32, 20), jnp.float32),
        "kv_norm": ((16,), jnp.float32),
        "kv_up": ((16, 4, 14), jnp.float32),
        "o_proj": ((4, 6, 32), jnp.float32)}
    for a, b in zip(jax.tree.leaves(trees[0]), jax.tree.leaves(trees[1]),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    from jax.sharding import PartitionSpec as P
    assert [mla.mla_leaf_spec(name, "tp") for name in sorted(trees[0])] == [
        P(), P(), P(None, "tp", None), P("tp", None, None),
        P(None, "tp", None)]


def test_rotary_of_halves_is_the_published_pairs_under_the_permutation():
    """``pairs_to_halves`` then half against half is the published rotary
    over interleaved pairs, regrouped: the columns before the rotated
    width pass as they are, and a product of two rotated vectors is the
    same either way."""
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 12))
    positions = jnp.broadcast_to(jnp.arange(9), (2, 9))
    halves = mla.pairs_to_halves(x, 8)
    np.testing.assert_array_equal(np.asarray(halves[..., :4]),
                                  np.asarray(x[..., :4]))
    np.testing.assert_array_equal(np.asarray(halves[..., 4:8]),
                                  np.asarray(x[..., 4::2]))
    np.testing.assert_array_equal(np.asarray(halves[..., 8:]),
                                  np.asarray(x[..., 5::2]))
    rotate = jax.jit(lambda x: rotary(x, positions, 1e6))
    got = rotate(halves[..., 4:])
    want = jax.jit(jax.vmap(lambda one: reference.rotary_pairs(one, 1e6)))(
        x[..., 4:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        mla.pairs_to_halves(want, 8)), rtol=1e-5, atol=1e-6)
    # position 0 is not turned, and a later one is
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(halves[:, 0, :, 4:]), rtol=1e-6)
    assert float(jnp.abs(got[:, 5] - halves[:, 5, :, 4:]).max()) > 1e-2
    # a key without a head axis turns as a head's does
    np.testing.assert_allclose(
        np.asarray(rotate(halves[:, :, 0, 4:])),
        np.asarray(got[:, :, 0]), rtol=1e-6)


def test_every_head_reads_one_rotated_key():
    k_n = jnp.zeros((2, 12, 4, 8))
    k_r = jax.random.normal(jax.random.key(3), (2, 12, 4))
    k = mla.whole_key(k_n, k_r)
    assert k.shape == (2, 12, 4, 12)
    for head in range(4):
        np.testing.assert_array_equal(np.asarray(k[:, :, head, 8:]),
                                      np.asarray(k_r))
    assert mla.score_scale(128, 64) == pytest.approx(192 ** -0.5)


def test_the_latent_norm_is_float32_in_a_bf16_layer():
    c = (jax.random.normal(jax.random.key(0), (2, 7, 16)) * 3).astype(
        jnp.bfloat16)
    weight = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (16,))
    got = mla.latent_norm(c, weight, 1e-6)
    assert got.dtype == jnp.bfloat16
    c32 = c.astype(jnp.float32)
    want = c32 / jnp.sqrt(jnp.mean(c32 ** 2, -1, keepdims=True) + 1e-6) \
        * weight
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want.astype(jnp.bfloat16)))


# ---- the flash kernels at two widths

def _plain(q, k, v, causal, scale):
    s = q.shape[1]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("shape, causal, blocks", [
    pytest.param((1, 256, 2, 2, 24, 16), True, (128, 128),
                 id="24-on-16-two-blocks"),
    pytest.param((2, 128, 4, 2, 48, 32), True, (None, None),
                 id="48-on-32-grouped-derived"),
    pytest.param((1, 128, 2, 2, 16, 40), False, (64, 32),
                 id="16-on-40-not-causal"),
])
def test_flash_kernels_take_a_value_width_apart_from_the_key_width(
        shape, causal, blocks):
    """Interpreted: the output and the three gradients against the plain
    formula, the values narrower than the keys and wider, grouped queries,
    a scale that is no power of two."""
    b, s, h, h_kv, d, d_v = shape
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k = jax.random.normal(keys[1], (b, s, h_kv, d))
    v = jax.random.normal(keys[2], (b, s, h_kv, d_v))
    weight = jax.random.normal(keys[3], (b, s, h, d_v))
    scale = (d + 3) ** -0.5

    def run(attend):
        def weighed(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out * weight), out

        return jax.jit(jax.value_and_grad(weighed, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = run(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, scale=scale, block_q=blocks[0],
            block_k=blocks[1]))
        want = run(lambda q, k, v: _plain(q, k, v, causal, scale))
    assert got[0][1].shape == (b, s, h, d_v)
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.shape == w.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-4,
                                   atol=2e-4)


def test_flash_refuses_widths_that_do_not_pair():
    q = jnp.zeros((1, 128, 2, 24))
    with pytest.raises(ValueError, match="query-key width"):
        fa.flash_attention(q, jnp.zeros((1, 128, 2, 16)), q)
    with pytest.raises(ValueError, match="query-key width"):
        fa.flash_attention(q, q, jnp.zeros((1, 128, 1, 16)))


# what each present cell's two kernels get, from the parent's rule (PR
# 45's ops/flash_attention.py): (block_q, block_k, streamed tile, the
# kernel's own vmem limit or None for the default scope)
@pytest.mark.parametrize("s, d, fwd, bwd", [
    pytest.param(1024, 64, (1024, 1024, 1024, None), (512, 512, 1024, None),
                 id="gpt2l-s1024"),
    pytest.param(4096, 64, (512, 1024, 4096, None),
                 (1024, 512, 4096, 25690112), id="gpt2l-s4096"),
    pytest.param(4096, 128, (512, 1024, 4096, None),
                 (1024, 512, 4096, 25690112), id="olmoe-s4096"),
    pytest.param(8192, 128, (512, 1024, 4096, None),
                 (1024, 512, 4096, 27787264), id="nemotron3s-s8192"),
    pytest.param(8192, 256, (512, 1024, 4096, 18612224),
                 (1024, 512, 4096, 42205184), id="qwen3next-s8192"),
    pytest.param(8192, 64, (512, 1024, 4096, None),
                 (1024, 512, 4096, 27787264), id="lfm2moe-s8192"),
])
def test_one_width_gets_the_tile_and_the_limit_it_had(s, d, fwd, bwd):
    """A caller that passes one width (every cell before this mixer's)
    gets the score tile, the streamed tile and the VMEM limit the rule
    gave before it took two; and naming the value width as the same
    changes nothing."""
    for kernel, want in (("fwd", fwd), ("bwd", bwd)):
        for d_v in (None, d):
            bq, bk = fa._derive_tile(kernel, s, d, 2, True, d_v)
            tile = fa._seq_tile(s, bq, bk)
            params = fa._compiler_params(kernel, bq, bk, d, 2, tile, s, d_v)
            assert (bq, bk, tile, params and params.vmem_limit_bytes) == want


def test_the_cells_two_widths_get_a_narrower_estimate_than_256():
    """192 on 128 at 2 x 8192: q and k take two 128-lane rows a position,
    v and o one, so the estimate lies between a head of 128's and a head
    of 256's, and the forward fits the default scope."""
    for kernel in ("fwd", "bwd"):
        bq, bk = fa._derive_tile(kernel, 8192, 192, 2, True, 128)
        assert (bq, bk) == fa._PREFERRED_TILE[kernel]
        need = lambda d, d_v=None: fa._vmem_bytes(kernel, bq, bk, d, 2,
                                                  4096, 8192, d_v)
        assert need(128) < need(192, 128) < need(256)
        assert need(192, 128) < need(192)
        assert need(256, 128) == need(192, 128)     # whole 128-lane rows
    assert fa._compiler_params("fwd", 512, 1024, 192, 2, 4096, 8192,
                               128) is None
    assert fa._compiler_params("bwd", 1024, 512, 192, 2, 4096, 8192,
                               128).vmem_limit_bytes == 39321600


@pytest.mark.parametrize("causal", [True, False])
def test_a_rotated_pair_gets_the_tile_and_the_limit_of_the_whole_width(
        causal, monkeypatch):
    """128 + 64 on 128 at 8192 positions, traced through both entries:
    each kernel's score tile, streamed tile and VMEM limit are those of
    192 on 128, argument for argument (128 + 64 takes the 256 lanes 192
    takes)."""
    asked = []
    real = fa._compiler_params
    monkeypatch.setattr(fa, "_compiler_params", lambda *a: (
        asked.append((a, getattr(real(*a), "vmem_limit_bytes", None))),
        real(*a))[1])
    like = lambda *dims: jax.ShapeDtypeStruct((1, 8192, *dims), jnp.bfloat16)
    jax.clear_caches()
    jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal).astype(jnp.float32).sum(), (0, 1, 2)),
        like(2, 192), like(2, 192), like(2, 128))
    whole, asked[:] = list(asked), []
    jax.eval_shape(jax.grad(lambda q_n, q_r, k_n, k_r, v: fa.flash_attention(
        q_n, k_n, v, q_r=q_r, k_r=k_r, causal=causal).astype(
            jnp.float32).sum(), (0, 1, 2, 3, 4)),
        like(2, 128), like(2, 64), like(2, 128), like(64), like(2, 128))
    assert asked == whole and [a[0][0] for a in asked] == ["fwd", "bwd"]
    if causal:
        assert [(a[1:3], a[5], limit) for a, limit in asked] == [
            ((512, 1024), 4096, None), ((1024, 512), 4096, 39321600)]


def test_mixer_sows_its_input_and_output():
    layer, params, x, positions = _mixer(12)
    out, sown = jax.jit(functools.partial(
        layer.apply, mutable=["intermediates"]))({"params": params}, x,
                                                 positions)
    kept = sown["intermediates"]
    np.testing.assert_array_equal(np.asarray(kept["mla_input"][0]),
                                  np.asarray(x))
    np.testing.assert_array_equal(np.asarray(kept["mla_output"][0]),
                                  np.asarray(out))


# ---- without a rotary (``rotary`` false: Kimi Linear's ``mla_use_nope``)

@pytest.mark.parametrize("seq, use_flash, dtype", [
    (24, False, jnp.float32), (128, True, jnp.float32),
    (128, True, jnp.bfloat16)])
def test_the_unrotated_mixer_matches_its_reference(seq, use_flash, dtype,
                                                   monkeypatch):
    """``rotary=False`` leaves step 4 and the weights' regrouping out: the
    mixer is ``chipbench/reference/kimi_linear.py``'s attention, a whole
    key ``k_n | k_r`` a head and nothing turned, by the einsum path and by
    the kernels on the four parts; its tree is the rotated mixer's, its
    output another."""
    from chipbench.reference import kimi_linear

    monkeypatch.setattr(kimi_linear, "QUERY_BLOCK", 16)
    layer, params, x, positions = _mixer(seq, dtype, use_flash)
    plain = layer.clone(rotary=False)
    assert jax.tree.map(jnp.shape, jax.eval_shape(
        plain.init, jax.random.key(0), x, positions)["params"]) \
        == jax.tree.map(jnp.shape, params)
    got = jax.jit(plain.apply)({"params": params}, x, positions)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda one, p: kimi_linear.latent_attention(one, p, _KANANA),
            in_axes=(0, None)))(x, params)
    rel = lambda a: float(jnp.linalg.norm(a - want) / jnp.linalg.norm(want))
    assert rel(got) <= (3e-2 if dtype == jnp.bfloat16 else 2e-5)
    assert rel(jax.jit(layer.apply)({"params": params}, x, positions)) > 0.1


def test_the_unrotated_mixer_names_no_rope_scope_and_hands_over_the_parts(
        monkeypatch):
    """No operation under ``mla_rope`` and no permutation of a weight's
    columns; the kernels get ``q_r`` and the one shared ``k_r`` as the
    projections left them; the rotated mixer's lowered text is what it was
    with the field named or not."""
    seen = {}
    right = fa.flash_attention

    def watch(q, k, v, **options):
        seen.update(q=q.shape, q_r=options["q_r"].shape,
                    k_r=options["k_r"].shape)
        return right(q, k, v, **options)

    monkeypatch.setattr(fa, "flash_attention", watch)
    layer, params, x, positions = _mixer(128, use_flash=True)
    plain = layer.clone(rotary=False)
    text = jax.jit(plain.apply).lower(
        {"params": params}, x, positions).as_text(debug_info=True)
    assert "mla_rope" not in text and "mla_core" in text
    assert seen == {"q": (2, 128, 4, 8), "q_r": (2, 128, 4, 4),
                    "k_r": (2, 128, 4)}
    turned = lambda m: jax.jit(m.apply).lower(
        {"params": params}, x, positions).as_text()
    assert "mla_rope" in jax.jit(layer.apply).lower(
        {"params": params}, x, positions).as_text(debug_info=True)
    assert turned(layer) == turned(layer.clone(rotary=True))
