"""Ring attention and Ulysses sequence parallelism vs. dense reference.

Runs on the 8-device virtual CPU mesh (conftest). Reference behavior:
the reference framework has no SP/CP (SURVEY.md §5.7); these tests define
the TPU framework's own correctness bar: sharded attention must match the
single-device dense computation to fp32 tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.mesh import make_parallel_mesh
from horovod_tpu.parallel.sequence import (
    ring_attention,
    ulysses_attention,
)


def _dense_reference(q, k, v, causal, scale=None):
    b, s, h, d = q.shape
    scale = scale or d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        pos = jnp.arange(s)
        mask = (pos[None, :] <= pos[:, None])[None, None]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _qkv(b=2, s=64, h=4, d=16, dtype=jnp.float32):
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    q, k, v = _qkv()
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh=mesh, causal=causal)
    ref = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_auto_resolves_per_shard(monkeypatch):
    """``use_flash="auto"`` is resolved INSIDE the shard function from
    its local block length — never by dividing a trace-time shape by a
    mesh factor at the call site, which double-divides when the caller
    is already inside its own shard_map (ADVICE r4). Pinned by spying
    on the resolver: with sp=8 over seq 64 it must see 8, not 1."""
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.parallel import sequence as seq_mod

    seen = []
    real = fa.resolve_flash

    def spy(use_flash, local_seq):
        seen.append(local_seq)
        return real(use_flash, local_seq)

    monkeypatch.setattr(fa, "resolve_flash", spy)
    q, k, v = _qkv()
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh=mesh, causal=True,
                         use_flash="auto")
    ref = _dense_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert seen and all(s == 64 // 8 for s in seen), seen
    del seq_mod  # imported to make the monkeypatch target explicit
    # what the shard function gets for the lengths it may see: the
    # kernels from the measured crossover up, on a TPU, and only where a
    # proper score tile divides the shard (einsum serves the rest)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [real("auto", s) for s in
            (8, fa._AUTO_FROM - 128, fa._AUTO_FROM, fa._AUTO_FROM + 64,
             8192)] == [False, False, True, False, True]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert real("auto", 8192) is False


@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_attention_gqa_circulates_small_kv(use_flash):
    """GQA K/V enter the ring UN-repeated (h_kv=2 circulating buffers
    for h=4 query heads — half the ICI payload); output must equal
    dense attention over locally-repeated K/V, on both the einsum and
    flash block paths."""
    rng = np.random.RandomState(1)
    b, s, h, h_kv, d = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh=mesh, causal=True,
                         use_flash=use_flash)
    kr = jnp.repeat(k, h // h_kv, axis=2)
    vr = jnp.repeat(v, h // h_kv, axis=2)
    ref = _dense_reference(q, kr, vr, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_gqa_ppermute_payload_is_small_kv():
    """The claim behind the GQA ring optimization, pinned at the IR
    level: the circulating ppermute buffers carry h_kv heads, not the
    query head count (the broadcast happens locally per block)."""
    from horovod_tpu.parallel.sequence import ring_attention_shard

    b, s_shard, h, h_kv, d = 1, 8, 4, 2, 16

    def shard_fn(q, k, v):
        return ring_attention_shard(q, k, v, axis_name="sp",
                                    causal=True)

    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    wrapped = jax.shard_map(shard_fn, mesh=mesh,
                            in_specs=(spec,) * 3, out_specs=spec,
                            check_vma=False)
    q = jnp.zeros((b, s_shard * 8, h, d), jnp.float32)
    k = jnp.zeros((b, s_shard * 8, h_kv, d), jnp.float32)
    jaxpr = jax.make_jaxpr(wrapped)(q, k, k)
    # walk the whole tree: the ppermutes live inside the scan eqn that
    # wraps the ring's fori_loop body, nested under the shard_map eqn
    perm_shapes = []

    def walk(jx):
        for e in jx.eqns:
            if e.primitive.name == "ppermute":
                perm_shapes.append(e.invars[0].aval.shape)
            for sub in e.params.values():
                if hasattr(sub, "eqns"):
                    walk(sub)
                elif hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)
    assert perm_shapes, "no ppermute found in the ring jaxpr"
    for shape in perm_shapes:
        assert shape[-2] == h_kv, (
            f"ring circulates {shape[-2]} heads; expected the small "
            f"K/V head count {h_kv}")


def test_ulysses_attention_gqa():
    """Ulysses with GQA: K/V heads exchange on their own (smaller)
    head axis; consecutive-query-head grouping survives the a2a."""
    rng = np.random.RandomState(2)
    b, s, h, h_kv, d = 2, 64, 16, 8, 8
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, mesh=mesh, causal=True)
    kr = jnp.repeat(k, h // h_kv, axis=2)
    vr = jnp.repeat(v, h // h_kv, axis=2)
    ref = _dense_reference(q, kr, vr, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_indivisible_kv_heads_raises():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.normal(size=(1, 64, 16, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 8)), jnp.float32)
    mesh = make_parallel_mesh(sp=8)
    with pytest.raises(ValueError, match="K/V heads"):
        ulysses_attention(q, k, k, mesh=mesh)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(causal):
    q, k, v = _qkv(h=8)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, mesh=mesh, causal=causal)
    ref = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_2d_mesh_dp_sp():
    """Ring attention composed with data parallelism on a dp×sp mesh."""
    q, k, v = _qkv(b=4, s=32)
    mesh = make_parallel_mesh(dp=2, sp=4)
    spec = P("dp", "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh=mesh, seq_specs=spec, causal=True)
    ref = _dense_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_jit_under_mesh():
    """ring attention shard fn embedded in a jitted program compiles once
    and matches; exercises the collective-inside-fori_loop path."""
    q, k, v = _qkv(s=32)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))

    @jax.jit
    def step(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True) * 2.0

    out = step(qs, ks, vs)
    ref = _dense_reference(q, k, v, True) * 2.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_flash_matches_dense():
    """use_flash routes the post-exchange local attention through the
    pallas kernel; must be numerically identical to the dense path."""
    q, k, v = _qkv(h=8)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, mesh=mesh, causal=True,
                            use_flash=True)
    ref = _dense_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_dense(causal):
    """use_flash on the RING path: per-block pallas kernel + lse combine
    must match the dense computation."""
    q, k, v = _qkv(s=64)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh=mesh, causal=causal,
                         use_flash=True)
    ref = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_gradients_match_dense():
    """The composition must be differentiable end-to-end: gradients flow
    through the kernel's lse output, the logaddexp combine, the masked
    branch of lax.switch, and ppermute."""
    q, k, v = _qkv(s=32)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))

    def loss_flash(q, k, v):
        o = ring_attention(q, k, v, mesh=mesh, causal=True,
                           use_flash=True)
        return (o.astype(jnp.float32) ** 2).mean()

    def loss_dense(q, k, v):
        return (_dense_reference(q, k, v, True).astype(jnp.float32)
                ** 2).mean()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(qs, ks, vs)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_ring_flash_bf16_matches_plain_ring_bf16():
    """In the production dtype the flash ring path must track the einsum
    ring path: both carry fp32 accumulators into the combine (the kernel
    writes out_dtype=fp32 for blockwise consumers)."""
    q, k, v = _qkv(s=64, dtype=jnp.bfloat16)
    mesh = make_parallel_mesh(sp=8)
    spec = P(None, "sp", None, None)
    qs, ks, vs = (jax.device_put(x, NamedSharding(mesh, spec))
                  for x in (q, k, v))
    o_flash = ring_attention(qs, ks, vs, mesh=mesh, causal=True,
                             use_flash=True)
    o_plain = ring_attention(qs, ks, vs, mesh=mesh, causal=True,
                             use_flash=False)
    assert o_flash.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(o_flash, np.float32), np.asarray(o_plain, np.float32),
        rtol=2e-2, atol=2e-2)
