"""Pallas flash attention vs. dense reference — forward and gradients.

Runs in interpret mode on the CPU test platform; the same kernels compile
for TPU (the driver's bench path)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention


def _dense(q, k, v, causal, scale=None):
    d = q.shape[-1]
    scale = scale or d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s = q.shape[1]
        pos = jnp.arange(s)
        scores = jnp.where((pos[None, :] <= pos[:, None])[None, None],
                           scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _qkv(b=2, s=128, h=2, d=32, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32),
                             dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_uneven_blocks():
    q, k, v = _qkv(s=96)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_dense(causal):
    q, k, v = _qkv(s=64)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(o * jnp.cos(o))

    def loss_dense(q, k, v):
        o = _dense(q, k, v, causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_bf16_forward():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                 v.astype(jnp.float32), True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_jit_compiles_once():
    q, k, v = _qkv(s=64)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    o1 = f(q, k, v)
    o2 = f(q * 1.0, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-6)


def test_lse_output_matches_dense_logsumexp():
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(s=64)
    b, s, h, d = q.shape
    o, lse = flash_attention_with_lse(q, k, v, causal=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    pos = jnp.arange(s)
    mask = (pos[None, :] <= pos[:, None])[None, None]
    scores = jnp.where(mask, scores, -1e30)
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)  # [B,H,S]
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(ref_lse.transpose(0, 2, 1)),
                               rtol=1e-5, atol=1e-5)


def test_lse_gradient_flows():
    """The lse output carries its own gradient (delta := delta − dlse in
    the backward kernels): a loss on lse alone must match the autodiff
    gradient of the dense logsumexp."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(s=32)
    b, s, h, d = q.shape

    def loss_flash(q, k, v):
        _, lse = flash_attention_with_lse(q, k, v, causal=True)
        return (lse ** 2).mean()

    def loss_dense(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * d ** -0.5
        pos = jnp.arange(s)
        mask = (pos[None, :] <= pos[:, None])[None, None]
        scores = jnp.where(mask, scores, -1e30)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        return (lse.transpose(0, 2, 1) ** 2).mean()

    gf = jax.grad(loss_flash, argnums=(0, 1))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_combined_o_and_lse_gradient():
    """Joint cotangents on (o, lse) — the exact pattern the ring combine
    produces — against the dense computation."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(s=32)
    b, s, h, d = q.shape

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=False)
        return (o.astype(jnp.float32) ** 2).mean() + (lse ** 2).mean()

    def loss_dense(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * d ** -0.5
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                       preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        return ((o ** 2).mean()
                + (lse.transpose(0, 2, 1) ** 2).mean())

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_seq_tile_divisibility_invariants():
    """Round-4 review pin: the streamed tile must divide the sequence
    AND be a multiple of both block sizes — the kernels walk
    ``tile // block`` sub-blocks, so a remainder would silently drop
    sequence positions (wrong results, no error)."""
    from horovod_tpu.ops.flash_attention import _seq_tile

    for s, bq, bk in [(768, 384, 256), (1024, 128, 128),
                      (8192, 128, 128), (384, 96, 128), (256, 256, 128),
                      (6144, 128, 512)]:
        t = _seq_tile(s, bq, bk)
        assert s % t == 0 and t % bq == 0 and t % bk == 0, (s, bq, bk, t)


def test_seq_tile_cap_bounds_the_bwd_tile(monkeypatch):
    """The streamed tile never exceeds the module's constant where a
    smaller valid one exists, for the backward (which streams Q AND dO
    together) as for the forward, while still satisfying the divisibility
    invariants; blocks whose lcm is above the constant get their lcm,
    the smallest tile that is correct."""
    from horovod_tpu.ops import flash_attention as fa

    assert fa._SEQ_TILE == 4096
    assert fa._seq_tile(8192, 128, 128) == 4096
    assert fa._plan("bwd", jax.ShapeDtypeStruct((1, 1, 8192, 64),
                                                jnp.bfloat16),
                    0.125, True, 128, 128).tile == 4096
    # the bound interacts with odd block sizes without breaking invariants
    t = fa._seq_tile(6144, 128, 512)
    assert t <= 4096 and 6144 % t == 0 and t % 512 == 0
    monkeypatch.setattr(fa, "_SEQ_TILE", 256)
    assert fa._seq_tile(8192, 128, 128) == 256
    assert fa._seq_tile(768, 384, 256) == 768


def test_flash_grads_match_dense_when_fwd_and_bwd_tiles_differ(
        monkeypatch):
    """Gradient correctness when the kernels' score tiles differ from one
    another and their streamed tiles from the sequence (the seq-8192
    shape, shrunk: seq 1536 under a constant of 512, where the forward's
    derived 384 x 768 and the backward's 768 x 384 stream two tiles of
    768, so dQ leaves its accumulator a tile at a time)."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_SEQ_TILE", 512)
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(1, 1536, 1, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 1536, 1, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 1536, 1, 32), jnp.float32)
    spec = jax.ShapeDtypeStruct((1, 1, 1536, 32), jnp.float32)
    plans = [fa._plan(kernel, spec, 1.0, True, None, None)
             for kernel in ("fwd", "bwd")]
    assert [(p.block_q, p.block_k, p.tile) for p in plans] == [
        (384, 768, 768), (768, 384, 768)]

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    def loss_dense(q, k, v):
        return _dense(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


def test_flash_multi_tile_matches_dense_768_mixed_blocks(monkeypatch):
    """The review's concrete miss case: s=768, block_q=384, block_k=256
    forces a tile that is a multiple of both; fwd AND grads must match
    the dense reference (pre-fix, dq dropped K positions 256..383)."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_SEQ_TILE", 256)  # force multi-tile paths
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(1, 768, 2, 32), jnp.float32)
    k = jnp.asarray(rs.randn(1, 768, 2, 32), jnp.float32)
    v = jnp.asarray(rs.randn(1, 768, 2, 32), jnp.float32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               block_q=384, block_k=256).sum()

    def loss_dense(q, k, v):
        return _dense(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("h,h_kv", [(4, 2), (4, 1), (6, 3)])
def test_flash_gqa_matches_dense_repeat(h, h_kv):
    """Grouped-query attention: the kernel reads shared K/V heads
    zero-copy (index-map aliasing); output AND all grads — including
    dk/dv through the per-query-head group-sum — must equal dense
    attention over repeat-expanded K/V."""
    rs = np.random.RandomState(7)
    S, D = 256, 16
    q = jnp.asarray(rs.randn(2, S, h, D), jnp.float32)
    k = jnp.asarray(rs.randn(2, S, h_kv, D), jnp.float32)
    v = jnp.asarray(rs.randn(2, S, h_kv, D), jnp.float32)
    g = h // h_kv

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_dense(q, k, v):
        kr = jnp.repeat(k, g, axis=-2)
        vr = jnp.repeat(v, g, axis=-2)
        return (_dense(q, kr, vr, causal=True) ** 2).sum()

    lf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ld, gd = jax.value_and_grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-4)
    for a, b in zip(gf, gd):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_gqa_rejects_indivisible_heads():
    q = jnp.zeros((1, 128, 4, 8), jnp.float32)
    kv = jnp.zeros((1, 128, 3, 8), jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, kv, kv)


@pytest.mark.parametrize("kernel, in_backward", [
    ("hvt_flash_fwd", False), ("hvt_flash_bwd", True)])
def test_kernels_carry_their_names(kernel, in_backward):
    # a device trace tells the two Pallas calls apart by these names
    q, k, v = _qkv(s=64)
    attend = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32).sum()
    forward = str(jax.make_jaxpr(attend)(q, k, v))
    backward = str(jax.make_jaxpr(jax.grad(attend, (0, 1, 2)))(q, k, v))
    assert (kernel in forward) == (not in_backward)
    assert kernel in backward
    # one pass over the score tiles: the backward is a single call
    assert backward.count("hvt_flash_bwd") == 1


def _dense_o_lse(q, k, v, causal):
    """The formula in float32: (o [B,S,H,D], lse [B,S,H]); K/V heads are
    repeated for a grouped-query shape."""
    d = q.shape[-1]
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") * d ** -0.5
    if causal:
        pos = jnp.arange(q.shape[1])
        scores = jnp.where(pos[None, :] <= pos[:, None], scores, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                   precision="highest")
    return o, jax.scipy.special.logsumexp(scores, -1).transpose(0, 2, 1)


# (seq, heads, kv_heads, causal, block_q, block_k, the module's _SEQ_TILE
# where the case patches it): None blocks are derived from the shape
@pytest.mark.parametrize("s,h,h_kv,causal,block_q,block_k,seq_tile", [
    pytest.param(256, 2, 2, True, None, None, None, id="derived-causal"),
    pytest.param(256, 2, 2, False, None, None, None, id="derived-full"),
    pytest.param(256, 2, 2, True, 128, 128, None, id="128x128-causal"),
    pytest.param(256, 2, 2, False, 128, 128, None, id="128x128-full"),
    # four k sub-blocks cross the diagonal of every Q block (fwd)
    pytest.param(512, 1, 1, True, 256, 64, None, id="256x64-diagonal"),
    # four Q sub-blocks cross it for every K block the backward keeps
    pytest.param(512, 1, 1, True, 64, 256, None, id="64x256-diagonal"),
    pytest.param(512, 1, 1, True, 128, 64, 256, id="multi-tile"),
    pytest.param(512, 1, 1, True, None, None, 256,
                 id="derived-multi-tile"),
    pytest.param(256, 4, 2, True, None, None, None, id="derived-gqa"),
    pytest.param(256, 4, 1, True, 128, 128, None, id="128x128-mqa"),
])
def test_score_tiles_match_the_f32_formula(s, h, h_kv, causal, block_q,
                                           block_k, seq_tile, monkeypatch):
    """o, lse, dq, dk, dv of every way a score tile is chosen against
    the float32 formula, cotangents on o and lse both."""
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    if seq_tile:
        monkeypatch.setattr(fa, "_SEQ_TILE", seq_tile)
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(1, s, h, 32), jnp.float32)
    k, v = (jnp.asarray(rs.randn(1, s, h_kv, 32), jnp.float32)
            for _ in range(2))
    w_o = jnp.asarray(rs.randn(1, s, h, 32), jnp.float32)
    w_lse = jnp.asarray(rs.randn(1, s, h), jnp.float32)

    def run(attend):
        def loss(q, k, v):
            o, lse = attend(q, k, v)
            return (o * w_o).sum() + (lse * w_lse).sum(), (o, lse)

        (_, (o, lse)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o, lse, *grads)

    got = run(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k))
    want = run(lambda q, k, v: _dense_o_lse(q, k, v, causal))
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1024, 1536, 2048, 4096, 8192, 16384])
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_derived_tile_divides_the_sequence_and_fits_the_budget(
        kernel, s, d, causal, itemsize):
    from horovod_tpu.ops import flash_attention as fa

    bq, bk = fa._derive_tile(kernel, s, d, itemsize, causal)
    assert s % bq == 0 and s % bk == 0, (bq, bk)
    assert bq % 128 == 0 and bk % 128 == 0, (bq, bk)
    assert max(bq, bk) % min(bq, bk) == 0, (bq, bk)
    most_q, most_k = fa._PREFERRED_TILE[kernel]
    assert bq <= max(most_q, s if causal else 0) and bk <= most_k
    tile = fa._seq_tile(s, bq, bk)
    assert tile % bq == 0 and tile % bk == 0 and tile <= 4096
    # inside the kernel's budget (the forward's the default scope, the
    # backward's the core's VMEM, its dQ accumulator counted), or the
    # old 128 x 128 when nothing is
    need = fa._vmem_bytes(kernel, bq, bk, d, itemsize, tile, s)
    assert need <= fa._VMEM_BUDGET[kernel] or (bq, bk) == (128, 128)
    if kernel == "bwd":
        assert need >= s * 128 * 4
    # bf16 gets the preferred tile wherever the sequence allows it,
    # but a causal sequence of one preferred tile (the test below)
    if itemsize == 2 and s % 1024 == 0 and not (causal and s == 1024):
        assert (bq, bk) == (most_q, most_k)
    # an explicit block is honoured as before, derived or not beside it
    assert fa._score_tile(kernel, s, d, itemsize, causal, 32, 384) == (
        32, fa._blocks(s, 384), False)
    assert fa._score_tile(kernel, s, d, itemsize, causal, None,
                          128) == (bq, 128, True)
    assert fa._score_tile(kernel, s, d, itemsize, causal, None,
                          None) == (bq, bk, True)


def _visited(kernel, s, block_q, block_k):
    """Score sub-blocks of the [s, s] square a causal call of ``kernel``
    passes through, as the kernels skip (``_causal_n_eff``; ``start``)."""
    n_q, n_k = s // block_q, s // block_k
    if kernel == "bwd":
        return sum(n_q - ki * block_k // block_q for ki in range(n_k))
    return sum(min(-(-(qi + 1) * block_q // block_k), n_k)
               for qi in range(n_q))


# what the v5e chose as (block_q, block_k, sub-blocks visited): the
# forward's in PRs 25 and 27, the one backward kernel's in PR 29, where
# dK/dV's tiles stayed the best or within 2% of it (PERF.md section 6)
_PREFERRED = {"fwd": (512, 1024), "bwd": (1024, 512)}
_CAUSAL_TILES = {
    ("fwd", 1024): (1024, 1024, 1),     # whole: a forward pass costs by
    ("bwd", 1024): (512, 512, 3),       # its rows whatever it skips
    ("fwd", 2048): (512, 1024, 6),      # (1024 x 512 at 1024: 2 of 2,
    ("bwd", 2048): (1024, 512, 6),      # 18% slower)
}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1024, 2048, 4096, 8192])
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_derived_tile_is_the_one_the_chip_chose(kernel, s, d, causal):
    from horovod_tpu.ops import flash_attention as fa

    got = fa._derive_tile(kernel, s, d, 2, causal)
    if not causal or s >= 4096:
        assert got == _PREFERRED[kernel]
        return
    bq, bk, visited = _CAUSAL_TILES[kernel, s]
    assert got == (bq, bk)
    assert _visited(kernel, s, bq, bk) == visited
    # less than the square of sub-blocks, but for the forward's one
    if (kernel, s) != ("fwd", 1024):
        assert visited < (s // bq) * (s // bk)


# (seq, head_dim) of the benchmark's cells (gpt2-large at 1024 and 4096,
# OLMoE's 4096 x 128) and some beyond them (the ring's local block of
# 16384 over four chips is 4096) -> the positions each kernel streams a
# grid step, as PR 27 computed them with no variable set: the whole
# sequence up to 4096, half of 8192, a quarter of 16384
@pytest.mark.parametrize("s,d,want", [
    (1024, 64, 1024), (4096, 64, 4096), (4096, 128, 4096),
    (8192, 64, 4096), (1536, 64, 1536), (2048, 64, 2048),
    (8192, 128, 4096), (16384, 64, 4096)])
@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_streamed_tile_is_the_one_the_cells_run_with(kernel, s, d, want):
    from horovod_tpu.ops import flash_attention as fa

    q = jax.ShapeDtypeStruct((1, 2, s, d), jnp.bfloat16)
    assert fa._plan(kernel, q, d ** -0.5, True, None, None).tile == want


@pytest.mark.parametrize("s", [1024, 2048])
def test_short_causal_tiles_match_the_einsum_path(s):
    """Value and gradients through every tile the rule returns for a
    short causal sequence (each kernel its own) against the einsum
    formula, interpreted."""
    q, k, v = _qkv(b=1, s=s, h=1, d=64, seed=3)
    w = jnp.asarray(np.random.RandomState(4).randn(*q.shape), jnp.float32)

    def run(attend):
        return jax.value_and_grad(
            lambda q, k, v: (attend(q, k, v) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: flash_attention(q, k, v, causal=True))
    want = run(lambda q, k, v: _dense(q, k, v, True))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s,want", [(64, 64), (96, 96), (192, 64),
                                    (1000, 8), (100, 100)])
def test_derived_tile_of_a_sequence_that_is_no_multiple_of_128(s, want):
    # the one block the old default of 128 gave
    from horovod_tpu.ops import flash_attention as fa

    for kernel in ("fwd", "bwd"):
        for causal in (True, False):
            assert fa._derive_tile(kernel, s, 64, 2, causal) == (
                want, want)


def _trace_count(**labels):
    from horovod_tpu import metrics

    labels.setdefault("d_rot", "0")     # q and k came whole
    labels.setdefault("window", "0")    # and every causal key is seen
    # the pieces by query rows a pass takes its sub-block in (``_chains``),
    # for float32 operands and a causal call
    from horovod_tpu.ops import flash_attention as fa
    labels.setdefault("chains", fa._chains(
        labels["kernel"].removesuffix("_choice"), int(labels["block_q"]),
        int(labels["block_k"]), 4, True))
    # what the forward's schedule leaves undone (PR 62): no step held in a
    # call of one tile, and the near edge's sub-block by its half where the
    # query block is shorter than a key block of whole lane tiles
    labels.setdefault("held_steps", "0")
    labels.setdefault("halves", int(
        labels["kernel"].startswith("fwd") and fa._takes_the_half(
            True, int(labels["block_q"]), int(labels["block_k"]))))
    m = metrics.registry().get("hvt_flash_kernel_traces_total")
    return m.labels(**labels).value if m else 0.0


def test_trace_counter_carries_the_tile_and_who_chose_it(monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    q, k, v = _qkv(b=1, s=256, h=1)
    kernels = ("fwd", "bwd")

    def counts(derived):
        out = []
        for kern in kernels:
            bq, bk = (fa._derive_tile(kern, 256, q.shape[-1],
                                      q.dtype.itemsize, True)
                      if derived else (32, 64))
            out.append(_trace_count(kernel=kern, block_q=str(bq),
                                    block_k=str(bk),
                                    derived=str(int(derived)),
                                    d_qk=q.shape[-1], d_v=v.shape[-1]))
        return out

    # two layers of one shape: each kernel is a jit of its own, traced
    # once for both (and once a process, hence the cleared caches); the
    # backward pass of both is the one kernel="bwd"
    trace = lambda **kw: jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(
            fa.flash_attention(q, k, v, **kw), k, v, **kw).sum(),
        (0, 1, 2)))(q, k, v)
    jax.clear_caches()
    explicit, derived = counts(False), counts(True)
    trace(block_q=32, block_k=64)
    assert counts(False) == [n + 1 for n in explicit]
    assert counts(True) == derived
    trace()
    assert counts(True) == [n + 1 for n in derived]
    trace()
    assert counts(True) == [n + 1 for n in derived]
    assert counts(False) == [n + 1 for n in explicit]
    # and in how many pieces by query rows each traced kernel takes its
    # sub-block: a forward block of 32 float32 rows halves on whole sublane
    # tiles; the backward's sub-blocks of 32 begin where its K blocks of
    # 64 do, so it has no use for the halves
    tile = dict(block_q="32", block_k="64", derived="0",
                d_qk=q.shape[-1], d_v=v.shape[-1])
    assert [_trace_count(kernel=kern, chains=n, **tile)
            for kern, n in (("fwd", 2), ("fwd", 1), ("bwd", 1), ("bwd", 2))
            ] == [explicit[0] + 1, 0, explicit[1] + 1, 0]
    # a key block shorter than the sub-block: the backward says that it
    # tells the sub-block's halves apart (PR 54)
    tile.update(block_q="128", block_k="64")
    halves = lambda: [_trace_count(kernel=kern, chains=n, **tile)
                      for kern, n in (("fwd", 2), ("bwd", 2), ("bwd", 1))]
    before = halves()
    trace(block_q=128, block_k=64)
    assert halves() == [before[0] + 1, before[1] + 1, before[2]]
    # what the forward's schedule leaves undone (PR 62): a query block of
    # 64 under a key block of 256 takes the diagonal's sub-block by its
    # half, and with the streamed tile at 256 of 1,024 positions 24 of the
    # 64 grid steps of a head's walk name the diagonal's tile again; the
    # backward, whose own rules ``chains`` tells, counts neither
    q, k, v = _qkv(b=1, s=1024, h=1)
    tile = dict(block_q="64", block_k="256", derived="0", d_qk=q.shape[-1],
                d_v=v.shape[-1])
    undone = lambda: [
        _trace_count(kernel="fwd", held_steps=n, halves=half, **tile)
        for n, half in ((24, 1), (0, 1), (24, 0))] + [
        _trace_count(kernel="bwd", chains=1, **tile)]
    before = undone()
    monkeypatch.setattr(fa, "_SEQ_TILE", 256)
    jax.clear_caches()
    trace(block_q=64, block_k=256)
    jax.clear_caches()
    assert undone() == [before[0] + 1, before[1], before[2], before[3] + 1]
    assert fa._held_steps(True, 8192, 512, 4096) == 8
    assert fa._held_steps(True, 16384, 512, 4096) == 48
    assert fa._held_steps(True, 4096, 512, 4096) == 0
    assert fa._held_steps(False, 8192, 512, 4096) == 0
    assert fa._held_steps(True, 16384, 512, 1024, window=2048) == 0


def test_trace_counter_adds_nothing_to_the_program(monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    q, k, v = _qkv(b=1, s=128, h=1)
    lowered = lambda: jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).sum(),
        (0, 1, 2))).lower(q, k, v).as_text()
    jax.clear_caches()
    widths = dict(d_qk=q.shape[-1], d_v=v.shape[-1])
    before = _trace_count(kernel="fwd", block_q="128", block_k="128",
                          derived="1", **widths)
    counted = lowered()
    assert _trace_count(kernel="fwd", block_q="128", block_k="128",
                        derived="1", **widths) == before + 1
    monkeypatch.setattr(fa, "_count_trace", lambda *a: None)
    jax.clear_caches()      # or the kernels' cached traces are served
    assert lowered() == counted


def test_scoped_vmem_is_raised_only_where_the_estimate_nears_it():
    # the default scope for the small tiles tests and old callers pass; a
    # limit of the estimate and XLA's 3 MiB where that is over it, and no
    # more (a wider scope slowed the step around the call: PERF.md section
    # 6, PR 29): for the backward at every long shape a cell runs, its dQ
    # accumulator included
    from horovod_tpu.ops import flash_attention as fa

    assert fa._compiler_params("fwd", 512, 1024, 64, 2, 4096, 4096) is None
    assert fa._compiler_params("fwd", 1024, 1024, 64, 2, 1024, 1024) is None
    assert fa._compiler_params("bwd", 128, 128, 64, 2, 1024, 1024) is None
    assert fa._compiler_params("bwd", 512, 512, 64, 2, 1024, 1024) is None
    for bq, bk, d, s in [(1024, 512, 64, 4096), (1024, 512, 128, 4096),
                         (1024, 512, 64, 8192)]:
        need = fa._vmem_bytes("bwd", bq, bk, d, 2, 4096, s)
        limit = fa._compiler_params("bwd", bq, bk, d, 2, 4096,
                                    s).vmem_limit_bytes
        assert fa._SCOPED_VMEM < limit == need + 3 * 2 ** 20 < 32 * 2 ** 20
    # the accumulator is the sequence's: 512 bytes a position of 128 lanes
    assert (fa._vmem_bytes("bwd", 1024, 512, 64, 2, 4096, 8192)
            - fa._vmem_bytes("bwd", 1024, 512, 64, 2, 4096, 4096)
            == 4096 * 128 * 4)


@pytest.mark.parametrize("kernel, took_mib", [("fwd", 16.31), ("bwd", 38.27)])
def test_scoped_vmem_at_a_head_of_256(kernel, took_mib):
    """Past 128 lanes the float32 [block, d] values of a sub-block count:
    at 2 x 8192 and d = 256 (qwen3next-s8192's attention) the limit each
    kernel is given covers what the v5e's compiler took for it (PERF.md
    section 6, PR 33), where the estimate without that term, with XLA's
    share, came to less; and at 64 and 128 lanes the term is nothing, so
    the figures the estimate was checked with stand."""
    from horovod_tpu.ops import flash_attention as fa

    bq, bk = fa._derive_tile(kernel, 8192, 256, 2, True)
    assert (bq, bk) == fa._PREFERRED_TILE[kernel]
    tile = fa._seq_tile(8192, bq, bk)
    limit = fa._compiler_params(kernel, bq, bk, 256, 2, tile,
                                8192).vmem_limit_bytes
    assert took_mib * 2 ** 20 < limit < (took_mib + 2.5) * 2 ** 20
    wide = 3 * (bq + bk) * (256 - 128) * 4
    for d in (64, 128):
        assert fa._vmem_bytes(kernel, bq, bk, d, 2, tile, 8192) == (
            fa._vmem_bytes(kernel, bq, bk, 256, 2, tile, 8192) - wide
            - {"fwd": 4 * tile * 256 + bq * (4 * 256 + 512),
               "bwd": 2 * tile * 3 * 256 + bk * (8 * 256 + 1024)
               + 8192 * 512}[kernel])


@pytest.mark.parametrize("s, fits", [(65536, True), (131072, True),
                                     (262144, False)])
def test_backward_names_a_sequence_its_accumulator_cannot_hold(s, fits):
    """The float32 dQ accumulator is as long as the sequence: past what a
    core's VMEM holds the backward pass says so at trace time, before
    Mosaic, and names the ways out; the forward, whose buffers are a
    tile's, traces at any length."""
    from horovod_tpu.ops import flash_attention as fa

    q = jax.ShapeDtypeStruct((1, s, 1, 64), jnp.bfloat16)
    loss = lambda q, k, v: flash_attention(q, k, v).astype(
        jnp.float32).sum()
    assert jax.eval_shape(loss, q, q, q).shape == ()
    if fits:
        grads = jax.eval_shape(jax.grad(loss, (0, 1, 2)), q, q, q)
        assert [g.shape for g in grads] == [q.shape] * 3
        bq, bk = fa._derive_tile("bwd", s, 64, 2, True)
        assert (bq, bk) == fa._PREFERRED_TILE["bwd"]
    else:
        with pytest.raises(ValueError, match="ring_attention"):
            jax.eval_shape(jax.grad(loss, (0, 1, 2)), q, q, q)


def _parent_gradients():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "flash_bwd_pr28.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1024, 2048])
def test_fused_backward_equals_the_two_kernels_it_replaced(s, d, causal):
    """dq, dk, dv of the one kernel against what PR 28's dQ and dK/dV
    kernels gave for the same bf16 operands at their derived tiles
    (interpreter; 48 fixed positions of each gradient and its norm,
    written by that commit's module from ``RandomState(s + d + causal)``).
    Same products, same operands, same order over the key blocks: they
    were equal to the bit when this was written; the tolerance is one
    bf16 rounding."""
    want = _parent_gradients()[f"{s}-{d}-{int(causal)}"]
    rs = np.random.RandomState(s + d + causal)
    q, k, v, w = (jnp.asarray(rs.randn(1, s, 1, d), jnp.bfloat16)
                  for _ in range(4))
    grads = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal=causal).astype(
            jnp.float32) * w.astype(jnp.float32)).sum(), (0, 1, 2))(q, k, v)
    at = np.random.RandomState(1).choice(s * d, 48, replace=False)
    for name, g in zip(("dq", "dk", "dv"), grads):
        g = np.asarray(g, np.float32)
        np.testing.assert_allclose(g.ravel()[at], want[name]["values"],
                                   rtol=2 ** -7, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.linalg.norm(g.astype(np.float64)),
                                   want[name]["norm"], rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [1024, 2048])
def test_fused_backward_matches_the_f32_formula(s, d, causal):
    """The backward kernel as ring attention calls it (``out_dtype``
    float32, cotangents on o and lse both) over a grouped-query pair of
    heads, at its derived tile, against the float32 formula."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(1, s, 2, d), jnp.float32)
    k, v = (jnp.asarray(rs.randn(1, s, 1, d), jnp.float32)
            for _ in range(2))
    w_o = jnp.asarray(rs.randn(1, s, 2, d), jnp.float32)
    w_lse = jnp.asarray(rs.randn(1, s, 2), jnp.float32)

    def grads(attend):
        def loss(q, k, v):
            o, lse = attend(q, k, v)
            assert o.dtype == jnp.float32
            return (o * w_o).sum() + (lse * w_lse).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=causal, out_dtype=jnp.float32))
    want = grads(lambda q, k, v: _dense_o_lse(q, k, v, causal))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_kernels_trace_under_shard_map_with_check_vma():
    """Value and gradients inside ``jax.shard_map`` as it is called by
    default (``check_vma=True`` wants every ``pallas_call`` output to say
    which mesh axes it varies over) equal the unsharded call's."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("world",))
    q, k, v = _qkv(b=4, s=128, h=2)

    def value_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: (flash_attention(q, k, v, causal=True)
                             ** 2).sum(), argnums=(0, 1, 2))(q, k, v)

    def per_chip(q, k, v):
        value, grads = value_and_grads(q, k, v)
        return jax.lax.psum(value, "world"), grads

    rows = P("world")
    got = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(rows,) * 3,
        out_specs=(P(), (rows,) * 3)))(q, k, v)
    want = value_and_grads(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ---- a rotated pair beside q and k: the score q k^T + q_r k_r^T

def _parts(b, s, h, n, e, d_v, dtype, seed=0):
    """``q_n, q_r, k_n, k_r, v`` and weights for o and lse: ``k_r`` has no
    head axis."""
    keys = jax.random.split(jax.random.key(seed), 7)
    shapes = ((b, s, h, n), (b, s, h, e), (b, s, h, n), (b, s, e),
              (b, s, h, d_v), (b, s, h, d_v), (b, s, h))
    *operands, w_o, w_lse = (jax.random.normal(key, shape).astype(dtype)
                             for key, shape in zip(keys, shapes))
    return operands, w_o.astype(jnp.float32), w_lse.astype(jnp.float32)


def _whole(q_n, q_r, k_n, k_r, v):
    """The operands of the one-width entry: ``q_n | q_r`` and ``k_n | k_r``
    with the one rotated key a position broadcast over the heads."""
    k_r = jnp.broadcast_to(k_r[:, :, None, :], k_n.shape[:-1] + k_r.shape[-1:])
    return (jnp.concatenate([q_n, q_r], -1),
            jnp.concatenate([k_n, k_r], -1), v)


def _formula_o_lse(q, k, v, causal, scale):
    """softmax(q k^T scale) v and the scores' log-sum-exp, float32."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        pos = jnp.arange(q.shape[1])
        scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v)
    return o, lse.transpose(0, 2, 1)


@pytest.mark.parametrize("s, blocks, seq_tile, causal, dtype", [
    pytest.param(256, (128, 64), None, True, jnp.float32, id="causal"),
    pytest.param(256, (64, 128), None, False, jnp.float32, id="not-causal"),
    pytest.param(256, (None, None), None, True, jnp.bfloat16,
                 id="bf16-derived"),
    pytest.param(256, (128, 128), None, False, jnp.bfloat16,
                 id="bf16-not-causal"),
    pytest.param(768, (None, None), 256, True, jnp.float32,
                 id="three-tiles-derived"),
    pytest.param(768, (128, 256), 256, True, jnp.float32,
                 id="three-tiles-mixed-blocks"),
])
def test_rotated_pair_matches_the_formula_and_the_one_width_entry(
        s, blocks, seq_tile, causal, dtype, monkeypatch):
    """``flash_attention_with_lse(q_n, k_n, v, q_r=.., k_r=..)``: o, lse
    and the five gradients under cotangents for both outputs, against the
    float32 formula on the assembled operands and against the one-width
    entry on them; ``k_r``'s gradient is the sum over the heads, which
    the broadcast's transpose makes on the other two paths. 16 and 8 on
    24, three heads, a scale that is no power of two (24^-1/2)."""
    from horovod_tpu.ops import flash_attention as fa

    if seq_tile:
        monkeypatch.setattr(fa, "_SEQ_TILE", seq_tile)
    operands, w_o, w_lse = _parts(2, s, 3, 16, 8, 24, dtype)
    scale = 24 ** -0.5
    tile = dict(zip(("block_q", "block_k"), blocks))

    def run(attend):
        def weighed(*operands):
            o, lse = attend(*operands)
            return (jnp.sum(o.astype(jnp.float32) * w_o)
                    + jnp.sum(lse * w_lse)), (o, lse)

        return jax.jit(jax.value_and_grad(
            weighed, argnums=(0, 1, 2, 3, 4), has_aux=True))(*operands)

    with jax.default_matmul_precision("highest"):
        got = run(lambda q_n, q_r, k_n, k_r, v: fa.flash_attention_with_lse(
            q_n, k_n, v, q_r=q_r, k_r=k_r, causal=causal, **tile))
        one_width = run(lambda *parts: fa.flash_attention_with_lse(
            *_whole(*parts), causal=causal, **tile))
        formula = run(lambda *parts: _formula_o_lse(*_whole(*parts), causal,
                                                    scale))
    (_, (o, lse)), grads = got
    assert o.shape == (2, s, 3, 24) and o.dtype == dtype
    assert lse.shape == (2, s, 3) and lse.dtype == jnp.float32
    assert [g.shape for g in grads] == [t.shape for t in operands]
    assert grads[3].shape == (2, s, 8)          # dk_r: no head axis
    # bf16: each path rounds o and the gradients once; float32: the sums'
    # order alone
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    for want in (one_width, formula):
        for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True):
            a, w = (np.asarray(t, np.float32) for t in (a, w))
            np.testing.assert_allclose(a, w, rtol=tol,
                                       atol=tol * np.abs(w).max())


def test_shared_rotated_keys_gradient_is_the_sum_over_the_heads():
    """With the heads' unrotated parts zero and one head's values alone
    read by the loss, every head still sends its share to the one
    ``k_r``: its gradient is the sum of what the one-width entry gives
    each head's copy."""
    from horovod_tpu.ops import flash_attention as fa

    (q_n, q_r, k_n, k_r, v), w_o, _ = _parts(1, 128, 4, 16, 8, 16,
                                             jnp.float32, seed=3)
    loss = lambda attend: lambda *t: jnp.sum(attend(*t) * w_o)
    with jax.default_matmul_precision("highest"):
        dk_r = jax.grad(loss(lambda q_n, q_r, k_n, k_r, v: fa.flash_attention(
            q_n, k_n, v, q_r=q_r, k_r=k_r)), argnums=3)(q_n, q_r, k_n, k_r, v)
        q, k, _ = _whole(q_n, q_r, k_n, k_r, v)
        dk = jax.grad(loss(fa.flash_attention), argnums=1)(q, k, v)
    assert dk_r.shape == k_r.shape
    a_head = np.asarray(dk[..., 16:])
    assert np.abs(a_head[:, :, 0] - a_head[:, :, 1]).max() > 1e-2
    np.testing.assert_allclose(np.asarray(dk_r), a_head.sum(axis=2),
                               rtol=1e-4, atol=1e-5)


def test_rotated_pair_refusals():
    """A ``q_r`` without a ``k_r`` and the other way, widths and shapes
    that do not pair, a ``k_r`` with a head axis, and grouped queries
    (not built: the rotated key is already shared by every head)."""
    from horovod_tpu.ops import flash_attention as fa

    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    q, v, q_r, k_r = z(1, 128, 4, 16), z(1, 128, 4, 8), z(1, 128, 4, 8), \
        z(1, 128, 8)
    with pytest.raises(ValueError, match="q_r alone"):
        fa.flash_attention(q, q, v, q_r=q_r)
    with pytest.raises(ValueError, match="k_r alone"):
        fa.flash_attention_with_lse(q, q, v, k_r=k_r)
    for bad_q_r, bad_k_r in ((q_r, z(1, 128, 4)),         # another width
                             (q_r, z(1, 128, 4, 8)),      # a key a head
                             (z(1, 128, 2, 8), k_r),      # not q's heads
                             (q_r, z(1, 64, 8))):         # not q's length
        with pytest.raises(ValueError, match="without a head axis"):
            fa.flash_attention(q, q, v, q_r=bad_q_r, k_r=bad_k_r)
    with pytest.raises(ValueError, match="as many key heads"):
        fa.flash_attention(q, z(1, 128, 2, 16), z(1, 128, 2, 8), q_r=q_r,
                           k_r=k_r)
    # and the pair changes nothing about what q, k and v must be
    with pytest.raises(ValueError, match="query-key width"):
        fa.flash_attention(q, z(1, 128, 4, 24), v, q_r=q_r, k_r=k_r)


def test_trace_counter_says_how_wide_a_rotated_pair_was():
    """``d_rot`` is 0 for a call whose q and k came whole and the pair's
    width for one that passed it, with ``d_qk`` the whole query-key width
    either way."""
    from horovod_tpu.ops import flash_attention as fa

    (q_n, q_r, k_n, k_r, v), _, _ = _parts(1, 128, 2, 16, 8, 16, jnp.float32)
    labels = dict(block_q="128", block_k="128", derived="1", d_v=16)
    count = lambda d_qk, d_rot: [
        _trace_count(kernel=kernel, d_qk=d_qk, d_rot=d_rot, **labels)
        for kernel in ("fwd", "bwd")]
    jax.clear_caches()
    before = count(24, 8), count(24, 0), count(16, 0)
    jax.eval_shape(jax.grad(lambda *t: fa.flash_attention(
        t[0], t[2], t[4], q_r=t[1], k_r=t[3]).sum()), q_n, q_r, k_n, k_r, v)
    assert (count(24, 8), count(24, 0), count(16, 0)) == (
        [n + 1 for n in before[0]], before[1], before[2])
    jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v).sum()), *_whole(q_n, q_r, k_n, k_r, v))
    assert (count(24, 8), count(24, 0), count(16, 0)) == (
        [n + 1 for n in before[0]], [n + 1 for n in before[1]], before[2])
    # the pair changes nothing about the chains: two in the forward
    assert [_trace_count(kernel="fwd", d_qk=24, d_rot=8, chains=n, **labels)
            for n in (2, 1)] == [before[0][0] + 1, 0]


# (kernel, block_q, block_k, itemsize, causal) -> chains: a block in two
# halves wherever a half is whole sublane tiles of the operands (16 rows of
# bf16, 8 of float32) and the kernel has a use for them: the forward always
# (two chains side by side), the backward where it is causal and its key
# block is shorter than the sub-block, so that a K block can begin past
# the sub-block's first half (that pass takes the second half alone)
@pytest.mark.parametrize("kernel, block_q, block_k, itemsize, causal, want", [
    ("fwd", 1024, 1024, 2, True, 2), ("fwd", 512, 512, 2, True, 2),
    ("fwd", 32, 32, 2, True, 2), ("fwd", 16, 16, 2, True, 1),
    ("fwd", 16, 16, 4, True, 2), ("fwd", 8, 8, 4, True, 1),
    ("fwd", 100, 100, 4, True, 1), ("fwd", 96, 96, 4, False, 2),
    ("bwd", 1024, 1024, 2, True, 1), ("bwd", 512, 512, 4, True, 1),
    ("bwd", 1024, 512, 2, True, 2), ("bwd", 1024, 512, 2, False, 1),
    ("bwd", 1024, 256, 2, True, 2), ("bwd", 512, 1024, 2, True, 1),
    ("bwd", 24, 12, 4, True, 1), ("bwd", 32, 16, 2, True, 2),
    ("bwd", 16, 8, 2, True, 1)])
def test_chains_go_by_the_tile_and_the_operands_itemsize(
        kernel, block_q, block_k, itemsize, causal, want):
    from horovod_tpu.ops import flash_attention as fa

    assert fa._chains(kernel, block_q, block_k, itemsize, causal) == want
    q = jax.ShapeDtypeStruct(
        (1, 1, block_q * block_k, 64),
        {2: jnp.bfloat16, 4: jnp.float32}[itemsize])
    assert fa._plan(kernel, q, 0.125, causal, block_q,
                    block_k).chains == want


def _chain_case(s, h, h_kv, d, d_v, e, dtype, seed):
    """``q, k, v, q_r, k_r`` (the pair None where ``e`` is 0) and weights
    for o and lse."""
    keys = jax.random.split(jax.random.key(seed), 7)
    shapes = ((1, s, h, d), (1, s, h_kv, d), (1, s, h_kv, d_v),
              (1, s, h, e), (1, s, e), (1, s, h, d_v), (1, s, h))
    q, k, v, q_r, k_r, w_o, w_lse = (
        jax.random.normal(key, shape) for key, shape in zip(keys, shapes))
    operands = [t.astype(dtype) for t in ((q, k, v, q_r, k_r) if e
                                          else (q, k, v))]
    return operands, w_o, w_lse


@pytest.mark.parametrize(
    "s, h, h_kv, d, d_v, e, causal, dtype, out_dtype, chains, blocks", [
        pytest.param(1024, 1, 1, 64, 64, 0, True, jnp.float32, None, 2, None,
                     id="s1024-d64-causal-one-sub-block-a-grid-step"),
        pytest.param(2048, 1, 1, 64, 64, 0, True, jnp.float32, None, 2, None,
                     id="s2048-d64-causal"),
        pytest.param(1024, 1, 1, 128, 128, 0, False, jnp.float32, None, 2, None,
                     id="s1024-d128-not-causal"),
        pytest.param(2048, 1, 1, 128, 128, 0, True, jnp.float32, None, 2, None,
                     id="s2048-d128-causal"),
        pytest.param(1024, 4, 1, 64, 64, 0, True, jnp.float32, None, 2, None,
                     id="grouped-4-on-1"),
        pytest.param(1024, 2, 2, 128, 128, 64, True, jnp.float32, None, 2, None,
                     id="rotated-128+64-on-128"),
        pytest.param(1024, 2, 2, 64, 64, 0, False, jnp.bfloat16,
                     jnp.float32, 2, None, id="bf16-out-float32-not-causal"),
        pytest.param(200, 2, 2, 64, 64, 0, True, jnp.float32, None, 1, None,
                     id="s200-no-multiple-of-128-one-chain"),
        pytest.param(96, 2, 2, 32, 32, 0, True, jnp.float32, None, 2, None,
                     id="s96-one-block-of-96-in-halves-of-48"),
        # a query block shorter than a key block of whole lane tiles: the
        # sub-block that holds a block's diagonal goes by its first half
        # where the block ends inside it (PR 62): every even block of six
        # at a half, the first two of every four at a quarter
        pytest.param(768, 1, 1, 32, 32, 0, True, jnp.float32, None, 2,
                     (128, 256), id="s768-128x256-even-blocks-by-the-half"),
        pytest.param(1024, 2, 1, 32, 32, 0, True, jnp.float32, None, 2,
                     (64, 256), id="s1024-64x256-grouped-2-on-1"),
        pytest.param(512, 2, 2, 32, 16, 8, True, jnp.float32, None, 2,
                     (128, 256), id="s512-128x256-rotated-32+8-on-16"),
        pytest.param(512, 2, 2, 32, 32, 0, True, jnp.bfloat16, jnp.float32,
                     2, (128, 256), id="s512-128x256-bf16"),
        pytest.param(512, 1, 1, 32, 32, 0, False, jnp.float32, None, 2,
                     (128, 256), id="s512-128x256-not-causal-no-half"),
    ])
def test_forward_in_chains_matches_the_formula_and_the_one_chain_pass(
        s, h, h_kv, d, d_v, e, causal, dtype, out_dtype, chains, blocks,
        monkeypatch):
    """The forward at the tile the rule derives (or at ``blocks``), with as
    many chains of query rows a pass as the rule gives that tile: o, lse
    and every gradient (cotangents for both outputs) against the float32
    formula, and equal to the last bit to what the same call gives with
    the rule held to one chain: a row of the score tile depends on no
    other row (to float32 rounding where the interpreter's products are
    of blocks the CPU's dot treats differently). And against the same call
    with every sub-block taken whole (``_takes_the_half`` held to no): the
    half that is left out holds no key a row of the block sees, so its p
    is 0 and it added 0; the traced forward kernel holds one branch more
    where it takes the half."""
    from horovod_tpu.ops import flash_attention as fa

    operands, w_o, w_lse = _chain_case(s, h, h_kv, d, d_v, e, dtype, seed=s)
    scale = (d + e) ** -0.5
    q_bhsd = jax.ShapeDtypeStruct((1, h, s, d), dtype)
    tile = dict(zip(("block_q", "block_k"), blocks or (None, None)))
    plan = fa._plan("fwd", q_bhsd, scale, causal, *tile.values(), d_v, e)
    assert plan.chains == chains

    def attend(q, k, v, q_r=None, k_r=None):
        return fa.flash_attention_with_lse(
            q, k, v, q_r=q_r, k_r=k_r, causal=causal, out_dtype=out_dtype,
            **tile)

    def branches():
        """``cond``s in the traced forward kernel."""
        return str(jax.make_jaxpr(lambda *t: attend(*t)[0])(
            *operands)).count(" cond[")

    def formula(q, k, v, q_r=None, k_r=None):
        if q_r is not None:
            q, k, v = _whole(q, q_r, k, k_r, v)
        k, v = (jnp.repeat(t, h // h_kv, axis=2) for t in (k, v))
        return _formula_o_lse(q, k, v, causal, scale)

    def run(attend):
        def weighed(*operands):
            o, lse = attend(*operands)
            return (jnp.sum(o.astype(jnp.float32) * w_o)
                    + jnp.sum(lse * w_lse)), (o, lse)

        return jax.jit(jax.value_and_grad(
            weighed, argnums=tuple(range(len(operands))),
            has_aux=True))(*operands)

    with jax.default_matmul_precision("highest"):
        jax.clear_caches()
        got, want, n_halved = run(attend), run(formula), branches()
        jax.clear_caches()
        monkeypatch.setattr(fa, "_takes_the_half", lambda *a: False)
        whole, n_whole = run(attend), branches()
        jax.clear_caches()
        monkeypatch.undo()
        monkeypatch.setattr(fa, "_chains", lambda *a: 1)
        one_chain = run(attend)
        jax.clear_caches()
    assert n_halved - n_whole == int(
        causal and plan.block_q < plan.block_k and plan.block_k % 256 == 0)
    (_, (o, lse)), grads = got
    assert o.dtype == (out_dtype or dtype) and lse.dtype == jnp.float32
    assert [g.shape for g in grads] == [t.shape for t in operands]
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        a, w = (np.asarray(t, np.float32) for t in (a, w))
        np.testing.assert_allclose(a, w, rtol=tol,
                                   atol=tol * np.abs(w).max())
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one_chain),
                    strict=True):
        if plan.block_q % 128 == 0:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:   # the CPU's dot sums a product of 48 rows in another order
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(whole),
                    strict=True):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(b).max()))


# ---- the backward's loop: nothing carried, and the sub-block a K block
# begins inside taken by its visible half (PR 54)

@pytest.mark.parametrize(
    "s, h, h_kv, d, d_v, e, causal, dtype, blocks, seq_tile, halved", [
        pytest.param(512, 2, 2, 32, 32, 0, True, jnp.float32, (128, 64),
                     None, True, id="plain"),
        pytest.param(512, 4, 1, 32, 32, 0, True, jnp.float32, (256, 128),
                     None, True, id="grouped-4-on-1"),
        pytest.param(512, 2, 2, 32, 16, 8, True, jnp.float32, (128, 64),
                     None, True, id="rotated-24+8-on-16"),
        pytest.param(512, 2, 2, 48, 32, 0, True, jnp.float32, (128, 64),
                     None, True, id="values-narrower-than-keys"),
        pytest.param(512, 2, 2, 32, 32, 0, True, jnp.bfloat16, (128, 64),
                     None, True, id="bf16"),
        pytest.param(768, 2, 2, 32, 32, 0, True, jnp.float32, (256, 128),
                     256, True, id="three-streamed-tiles"),
        pytest.param(2048, 1, 1, 64, 64, 0, True, jnp.float32, (None, None),
                     None, True, id="s2048-derived-1024x512"),
        pytest.param(512, 2, 2, 32, 32, 0, False, jnp.float32, (128, 64),
                     None, False, id="not-causal-every-pass-whole"),
        pytest.param(512, 2, 2, 32, 32, 0, True, jnp.float32, (128, 128),
                     None, False, id="square-tile-no-block-begins-inside"),
        pytest.param(512, 2, 2, 32, 32, 0, True, jnp.float32, (128, 32),
                     None, True, id="k-block-a-quarter-of-the-sub-block"),
        pytest.param(192, 2, 2, 32, 32, 0, True, jnp.float32, (24, 12),
                     None, False, id="24-rows-halve-inside-a-sublane-tile"),
    ])
def test_backward_carries_nothing_and_takes_the_visible_half(
        s, h, h_kv, d, d_v, e, causal, dtype, blocks, seq_tile, halved,
        monkeypatch):
    """Every gradient (cotangents for o and lse) against the float32
    formula, at tiles whose K block begins in the middle of a query
    sub-block and at tiles where none does; and against the same call
    with every pass taken whole (``_chains`` held to one): the half that
    is left out sees no key of the block, so its p is 0 and it added 0.
    ``halved`` says whether the traced backward kernel holds the one
    branch more that takes the half pass."""
    from horovod_tpu.ops import flash_attention as fa

    if seq_tile:
        monkeypatch.setattr(fa, "_SEQ_TILE", seq_tile)
    operands, w_o, w_lse = _chain_case(s, h, h_kv, d - e, d_v, e, dtype,
                                       seed=s + e)
    scale = d ** -0.5
    tile = dict(zip(("block_q", "block_k"), blocks))

    def attend(q, k, v, q_r=None, k_r=None):
        return fa.flash_attention_with_lse(q, k, v, q_r=q_r, k_r=k_r,
                                           causal=causal, **tile)

    def formula(q, k, v, q_r=None, k_r=None):
        if q_r is not None:
            q, k, v = _whole(q, q_r, k, k_r, v)
        k, v = (jnp.repeat(t, h // h_kv, axis=2) for t in (k, v))
        return _formula_o_lse(q, k, v, causal, scale)

    def grads(attend):
        def weighed(*operands):
            o, lse = attend(*operands)
            return (jnp.sum(o.astype(jnp.float32) * w_o)
                    + jnp.sum(lse * w_lse))

        return jax.jit(jax.grad(weighed, argnums=tuple(range(
            len(operands)))))(*operands)

    def branches():
        """``cond``s in the traced backward kernel."""
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *t: attend(*t)[0].astype(jnp.float32).sum(),
            argnums=0))(*operands)
        return str(jaxpr).count(" cond[")

    with jax.default_matmul_precision("highest"):
        jax.clear_caches()
        got, want, n_halved = grads(attend), grads(formula), branches()
        jax.clear_caches()
        monkeypatch.setattr(fa, "_chains", lambda *a: 1)
        whole, n_whole = grads(attend), branches()
        jax.clear_caches()
    assert n_halved - n_whole == int(halved)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    for g, w, t in zip(got, want, operands, strict=True):
        assert g.shape == t.shape and g.dtype == t.dtype
        g, w = (np.asarray(x, np.float32) for x in (g, w))
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())
    for g, w in zip(got, whole, strict=True):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=1e-5, atol=1e-6 * float(np.abs(np.asarray(w,
                                                           np.float32)).max()))


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def _block_index(mapping, *step):
    """The block a ``pallas_call``'s operand takes at a grid step."""
    closed = mapping.index_map_jaxpr
    return tuple(int(i) for i in jax.core.eval_jaxpr(
        closed.jaxpr, closed.consts, *(jnp.int32(i) for i in step)))


@pytest.mark.parametrize("causal, with_choice", [
    (True, False), (True, True), (False, False)])
def test_backward_fetches_no_tile_its_k_block_sees_nothing_of(
        causal, with_choice, monkeypatch):
    """The block indices the backward call's streamed operands (q, dO, lse,
    delta and a choice's rows) take over the grid, read from the traced
    ``pallas_call``: a causal K block's grid steps before the tile it
    begins in name that tile, so the pipeline fetches nothing for the
    steps that compute nothing; k, v and the outputs go by the step."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_SEQ_TILE", 256)
    s, block_q, block_k = 1024, 256, 128
    q, k, v = _qkv(b=1, s=s, h=1)
    choice = ({"choice": jnp.asarray(np.tril(np.ones((1, s, s))), jnp.int8)}
              if with_choice else {})
    jax.clear_caches()
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        **choice).sum(), (0, 1, 2)))(q, k, v)
    jax.clear_caches()
    bwd, = (e for e in _pallas_eqns(jaxpr.jaxpr)
            if e.params["name"] == "hvt_flash_bwd")
    mappings = bwd.params["grid_mapping"].block_mappings
    n_in = 7 if with_choice else 6

    for ki in range(s // block_k):
        for ti in range(s // 256):
            first = max(ti, ki * block_k // 256) if causal else ti
            got = [_block_index(m, 0, 0, ki, ti) for m in mappings]
            assert got[:2] == [(0, 0, ki, 0)] * 2            # k, v
            assert got[2:6] == [(0, 0, first, 0)] * 4   # q, dO, lse, delta
            if with_choice:
                assert got[6] == (0, first, ki)
            # dq leaves with the last K block; dk and dv go by the block
            last = ki == s // block_k - 1
            assert got[n_in:] == [(0, 0, ti if last else 0, 0),
                                  (0, 0, ki, 0), (0, 0, ki, 0)]


@pytest.mark.parametrize("causal, seq_tile, extra", [
    pytest.param(True, 256, None, id="plain"),
    pytest.param(True, 256, "rotated", id="rotated-pair"),
    pytest.param(True, 256, "choice", id="choice"),
    pytest.param(True, 4096, "choice", id="one-tile"),
    pytest.param(False, 256, None, id="not-causal"),
])
def test_forward_fetches_no_tile_past_its_blocks_diagonal(
        causal, seq_tile, extra, monkeypatch):
    """The block indices the forward call's streamed operands (K, V, a
    rotated ``k_r`` and a choice's columns) take over the grid, read from
    the traced ``pallas_call``: a causal query block's grid steps past the
    tile that holds its diagonal name that tile, so the pipeline fetches
    nothing for the steps that compute nothing; q and the outputs go by
    the block. A call of one tile and one that is not causal name what
    they named, through index maps without a minimum in them. And ``o``,
    ``lse`` and every gradient are the one-tile call's to the last bit."""
    from horovod_tpu.ops import flash_attention as fa

    s, block_q, block_k, e = 1024, 128, 256, 8
    operands, w_o, w_lse = _chain_case(
        s, 2, 2, 32, 32, e if extra == "rotated" else 0, jnp.float32, seed=7)
    chosen = ({"choice": jnp.asarray(np.tril(np.ones((1, s, s))), jnp.int8)}
              if extra == "choice" else {})

    def weighed(*operands):
        pair = dict(zip(("q_r", "k_r"), operands[3:]))
        o, lse = fa.flash_attention_with_lse(
            *operands[:3], causal=causal, block_q=block_q, block_k=block_k,
            **pair, **chosen)
        return jnp.sum(o * w_o) + jnp.sum(lse * w_lse), (o, lse)

    run = jax.value_and_grad(weighed, argnums=tuple(range(len(operands))),
                             has_aux=True)
    monkeypatch.setattr(fa, "_SEQ_TILE", seq_tile)
    tile = min(seq_tile, s)
    jax.clear_caches()
    fwd, = (eqn for eqn in _pallas_eqns(jax.make_jaxpr(weighed)(
        *operands).jaxpr) if eqn.params["name"] == "hvt_flash_fwd")
    mappings = fwd.params["grid_mapping"].block_mappings
    assert fwd.params["grid_mapping"].grid == (1, 2, s // block_q, s // tile)

    holds = causal and tile < s
    # k and v, and k_r after q_r or a choice's columns
    streamed = {1, 2, *({"rotated": [4], "choice": [3]}.get(extra, []))}
    for i, mapping in enumerate(mappings):
        assert (" min " in str(mapping.index_map_jaxpr)) == (
            holds and i in streamed)
    for qi in range(s // block_q):
        for ti in range(s // tile):
            held = min(ti, ((qi + 1) * block_q - 1) // tile) if holds else ti
            got = [_block_index(m, 0, 1, qi, ti) for m in mappings]
            assert got[0] == (0, 1, qi, 0)                        # q
            assert got[1:3] == [(0, 1, held, 0)] * 2              # k, v
            if extra == "rotated":
                assert got[3:5] == [(0, 1, qi, 0), (0, held, 0)]  # q_r, k_r
            if extra == "choice":
                assert got[3] == (0, qi, held)
            assert got[-2:] == [(0, 1, qi, 0)] * 2                # o, lse
    got = run(*operands)
    monkeypatch.setattr(fa, "_SEQ_TILE", 4096)
    jax.clear_caches()
    one_tile = run(*operands)
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one_tile),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- a choice of keys: a mask a query row, the same for every head

def _choice(b, s, keep, causal=True, seed=3):
    """A mask ``[b, s, s]`` int8 of about ``keep`` of each row's (causal)
    keys, every row seeing at least its first key."""
    rng = np.random.RandomState(seed)
    mask = rng.uniform(size=(b, s, s)) < keep
    if causal:
        mask &= np.tril(np.ones((s, s), bool))
    mask[:, :, 0] = True
    return jnp.asarray(mask, jnp.int8)


def _dense_chosen(q, k, v, choice, scale=None):
    """Einsums with the same mask, float32 softmax, ``(o, lse)``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    scale = scale or q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where((choice != 0)[:, None], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v)
    return out, jnp.transpose(lse, (0, 2, 1))


@pytest.mark.parametrize("s, h, h_kv, tile, causal, keep", [
    (256, 4, 2, {}, True, 0.3),
    (256, 4, 1, dict(block_q=64, block_k=128), True, 0.1),
    (384, 2, 2, dict(block_q=128, block_k=64), True, 0.02),
    (256, 4, 2, dict(block_q=128, block_k=128), False, 0.3),
    # a K block that begins in the middle of a sub-block: the backward
    # takes that sub-block's second half alone (PR 54)
    (256, 4, 2, dict(block_q=128, block_k=64), True, 0.3),
    (512, 2, 1, dict(block_q=256, block_k=128), True, 0.05),
    (256, 2, 2, dict(block_q=128, block_k=64), False, 0.3),
    # a query block that ends in the first half of a key sub-block: the
    # forward takes the choice's columns of that half alone (PR 62)
    (512, 2, 1, dict(block_q=128, block_k=256), True, 0.05),
])
def test_choice_matches_the_einsum_with_the_same_mask(s, h, h_kv, tile,
                                                      causal, keep):
    """Forward (o and lse) and the three gradients, grouped queries, rows
    that see no key of a sub-block (``keep`` 0.02 leaves most sub-blocks
    of a row empty), derived and named tiles, in the interpreter."""
    from horovod_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(5)
    mk = lambda heads: jnp.asarray(rng.normal(size=(2, s, heads, 32)),
                                   jnp.float32)
    q, k, v = mk(h), mk(h_kv), mk(h_kv)
    choice = _choice(2, s, keep, causal)
    w_o, w_lse = mk(h), jnp.asarray(rng.normal(size=(2, s, h)), jnp.float32)

    def loss(attend):
        def f(q, k, v):
            o, lse = attend(q, k, v)
            return jnp.sum(o * w_o) + jnp.sum(lse * w_lse), (o, lse)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, (o, lse)), grads = loss(lambda q, k, v: fa.flash_attention_with_lse(
        q, k, v, choice=choice, causal=causal, **tile))(q, k, v)
    (_, (o_w, lse_w)), want = loss(lambda q, k, v: _dense_chosen(
        q, k, v, choice))(q, k, v)
    np.testing.assert_allclose(o, o_w, atol=2e-5)
    np.testing.assert_allclose(lse, lse_w, atol=2e-5)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(g, w, atol=1e-4)


def test_a_causal_choice_is_the_causal_call():
    """The lower triangle as a choice gives what ``causal=True`` gives."""
    q, k, v = _qkv(b=1, s=256, h=2)
    lower = jnp.asarray(np.tril(np.ones((1, 256, 256))), jnp.int8)
    np.testing.assert_allclose(
        flash_attention(q, k, v, choice=lower), flash_attention(q, k, v),
        atol=1e-6)


@pytest.mark.parametrize("bad, match", [
    (dict(q_r=True), "beside a rotated pair is not built"),
    (dict(dtype=jnp.int32), "a choice is int8"),
    (dict(dtype=jnp.bool_), "a choice is int8"),
    (dict(shape=(1, 128, 64)), "a choice is int8"),
    (dict(shape=(1, 2, 128, 128)), "a choice is int8"),
])
def test_choice_refusals(bad, match):
    """What the kernels do not build is refused by name: a choice beside a
    rotated pair, a mask of another type, and one a head or of another
    length."""
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    q = z(1, 128, 2, 16)
    choice = jnp.ones(bad.get("shape", (1, 128, 128)),
                      bad.get("dtype", jnp.int8))
    pair = dict(q_r=z(1, 128, 2, 8), k_r=z(1, 128, 8)) if "q_r" in bad else {}
    with pytest.raises(ValueError, match=match):
        flash_attention(q, q, q, choice=choice, **pair)


def test_grouped_queries_beside_a_rotated_pair_are_refused_by_name():
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match="as many key heads as query heads"):
        flash_attention(z(1, 128, 4, 16), z(1, 128, 2, 16), z(1, 128, 2, 16),
                        q_r=z(1, 128, 4, 8), k_r=z(1, 128, 8))


def test_a_call_without_a_choice_traces_what_it_traced_before():
    """A call with a choice counts under ``fwd_choice`` and ``bwd_choice``
    (same label names); a call without one under ``fwd`` and ``bwd`` with
    the labels it had, its kernels' arguments hold no choice, and its tile
    and VMEM estimate are the ones it had."""
    from horovod_tpu.ops import flash_attention as fa

    q, k, v = _qkv(b=1, s=256, h=2)
    choice = _choice(1, 256, 0.3)
    labels = dict(block_q="256", block_k="256", derived="1", d_qk=32, d_v=32)
    count = lambda suffix: [
        _trace_count(kernel=kernel + suffix, **labels)
        for kernel in ("fwd", "bwd")]
    jax.clear_caches()
    before = count(""), count("_choice")
    with_choice = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, choice=choice).sum(), (0, 1, 2)))(q, k, v)
    assert (count(""), count("_choice")) == (
        before[0], [n + 1 for n in before[1]])
    without = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v).sum(), (0, 1, 2)))(q, k, v)
    assert (count(""), count("_choice")) == (
        [n + 1 for n in before[0]], [n + 1 for n in before[1]])
    assert "i8[" in str(with_choice) and "i8[" not in str(without)
    for kernel in ("fwd", "bwd"):
        assert fa._derive_tile(kernel, 16384, 128, 2, True) == \
            fa._derive_tile(kernel, 16384, 128, 2, True, None, False)
        args = (kernel, 512, 512, 128, 2, 4096, 16384)
        assert fa._vmem_bytes(*args) == fa._vmem_bytes(*args, None, False)
        assert fa._vmem_bytes(*args, None, True) == \
            fa._vmem_bytes(*args) + 2 * 4096 * 512
