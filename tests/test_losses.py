"""Chunked LM cross-entropy vs the naive materialized computation
(ops/losses.py — value and gradients must match exactly; the chunking is
a pure memory optimization)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


def _naive(hidden, emb, targets):
    logits = jnp.einsum("bsd,vd->bsv", hidden.astype(jnp.float32),
                        emb.astype(jnp.float32))
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, targets).mean()


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_fused_ce_matches_naive_value_and_grads(chunk):
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    rng = np.random.RandomState(0)
    b, s, d, v = 2, 16, 8, 37
    hidden = jnp.asarray(rng.randn(b, s, d), jnp.float32)
    emb = jnp.asarray(rng.randn(v, d) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, v, (b, s)))

    l0, (gh0, ge0) = jax.value_and_grad(_naive, argnums=(0, 1))(
        hidden, emb, targets)
    l1, (gh1, ge1) = jax.jit(jax.value_and_grad(
        lambda h, e: softmax_cross_entropy_fused(h, e, targets,
                                                 chunk=chunk),
        argnums=(0, 1)))(hidden, emb)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh0),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ge1), np.asarray(ge0),
                               rtol=1e-5, atol=1e-7)


def test_fused_ce_bf16_hidden():
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    rng = np.random.RandomState(1)
    hidden = jnp.asarray(rng.randn(2, 8, 16), jnp.bfloat16)
    emb = jnp.asarray(rng.randn(33, 16) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, 33, (2, 8)))
    l_f = softmax_cross_entropy_fused(hidden, emb, targets, chunk=4)
    l_n = _naive(hidden, emb, targets)
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_n),
                               rtol=1e-6)


@pytest.mark.parametrize("s", [10, 31, 127])
def test_fused_ce_non_divisible_seq_pads_and_masks(s):
    """Odd sequence lengths (the bench call site slices to seq-1 = odd!)
    must keep the REQUESTED chunk via pad+mask — value and grads still
    exact, never a degenerate chunk=1 scan."""
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    rng = np.random.RandomState(2)
    hidden = jnp.asarray(rng.randn(2, s, 8), jnp.float32)
    emb = jnp.asarray(rng.randn(21, 8) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.randint(0, 21, (2, s)))
    l0, g0 = jax.value_and_grad(_naive)(hidden, emb, targets)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda h: softmax_cross_entropy_fused(h, emb, targets,
                                              chunk=8)))(hidden)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=1e-5, atol=1e-7)


def test_fused_ce_rejects_bad_chunk():
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    with pytest.raises(ValueError, match="chunk"):
        softmax_cross_entropy_fused(jnp.zeros((1, 4, 2)),
                                    jnp.zeros((5, 2)),
                                    jnp.zeros((1, 4), jnp.int32), chunk=0)


def test_gpt_chunked_ce_trains_identically():
    """GPT(return_hidden) + fused CE must produce the same loss and
    gradients as the logits path (a pure memory optimization)."""
    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    model = GPT(cfg)
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 64, (2, 16)))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    targets = jnp.roll(tokens, -1, axis=-1)

    def loss_logits(p):
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], targets[:, :-1]).mean()

    def loss_fused(p):
        hidden = model.apply({"params": p}, tokens, return_hidden=True)
        return softmax_cross_entropy_fused(
            hidden[:, :-1], p["embedding"], targets[:, :-1], chunk=5)

    l0, g0 = jax.jit(jax.value_and_grad(loss_logits))(params)
    l1, g1 = jax.jit(jax.value_and_grad(loss_fused))(params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# ---- the gradients made in the pass that makes the logits (PR 42)

def _case(seed, b, s, d, v, hidden_dtype=jnp.float32,
          emb_dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, s, d), hidden_dtype),
            jnp.asarray(rng.randn(v, d) * 0.1, emb_dtype),
            jnp.asarray(rng.randint(0, v, (b, s))))


def _lowered_grad(hidden, emb, targets, chunk):
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    return jax.jit(jax.grad(
        lambda h, e: softmax_cross_entropy_fused(h, e, targets, chunk=chunk),
        argnums=(0, 1))).lower(hidden, emb).as_text()


@pytest.mark.parametrize("emb_dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ce_bf16_hidden_value_and_grads(emb_dtype):
    """bf16 hidden states (what the cells pass): value and both gradients
    against the naive computation, each gradient in its operand's dtype."""
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    hidden, emb, targets = _case(4, 2, 24, 16, 33, jnp.bfloat16, emb_dtype)
    l0, (gh0, ge0) = jax.value_and_grad(_naive, argnums=(0, 1))(
        hidden, emb, targets)
    l1, (gh1, ge1) = jax.jit(jax.value_and_grad(
        lambda h, e: softmax_cross_entropy_fused(h, e, targets, chunk=8),
        argnums=(0, 1)))(hidden, emb)
    assert gh1.dtype == jnp.bfloat16 and ge1.dtype == emb_dtype
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    # one rounding to bf16 of the same float32 product on both sides
    np.testing.assert_allclose(np.asarray(gh1, np.float32),
                               np.asarray(gh0, np.float32),
                               rtol=1e-2, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ge1, np.float32),
                               np.asarray(ge0, np.float32),
                               rtol=1e-2 if emb_dtype == jnp.bfloat16
                               else 1e-5, atol=1e-6)


@pytest.mark.parametrize("cotangent", ["literal", "traced"])
def test_fused_ce_scales_by_the_incoming_cotangent(cotangent):
    """``3.7 * loss + other(hidden)``: the backward rule multiplies the
    kept gradients by whatever reaches it, a constant or a traced value."""
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    hidden, emb, targets = _case(5, 2, 16, 8, 29)

    def total(ce, h, e, scale):
        return scale * ce(h, e) + (h ** 2).sum() * 0.01

    def fused(h, e):
        return softmax_cross_entropy_fused(h, e, targets, chunk=8)

    def naive(h, e):
        return _naive(h, e, targets)

    if cotangent == "literal":
        grad = lambda ce: jax.grad(  # noqa: E731
            lambda h, e: total(ce, h, e, 3.7), argnums=(0, 1))(hidden, emb)
    else:
        grad = lambda ce: jax.jit(jax.grad(  # noqa: E731
            lambda h, e, scale: total(ce, h, e, scale ** 2),
            argnums=(0, 1)))(hidden, emb, jnp.float32(1.9))
    for got, want in zip(grad(fused), grad(naive)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)


def test_fused_ce_tied_matrix_used_twice():
    """A tied model: the matrix is the embedding gather's operand and the
    head's, and its gradient is the sum of both uses."""
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    _, emb, targets = _case(6, 2, 16, 8, 27)
    tokens = jnp.roll(targets, 1, axis=-1)

    def tied(ce):
        return jax.grad(lambda e: ce(jnp.tanh(e[tokens]), e))(emb)

    got = tied(lambda h, e: softmax_cross_entropy_fused(
        h, e, targets, chunk=4))
    want = tied(lambda h, e: _naive(h, e, targets))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("s", [10, 31])
def test_fused_ce_padded_length_grads_at_the_requested_chunk(s):
    """A length no chunk divides: both gradients exact, ``hidden``'s of the
    unpadded shape, and the program's logits ``[batch, chunk, vocab]``."""
    hidden, emb, targets = _case(7, 2, s, 8, 21)
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    gh0, ge0 = jax.grad(_naive, argnums=(0, 1))(hidden, emb, targets)
    gh1, ge1 = jax.jit(jax.grad(
        lambda h, e: softmax_cross_entropy_fused(h, e, targets, chunk=8),
        argnums=(0, 1)))(hidden, emb)
    assert gh1.shape == hidden.shape
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh0),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ge1), np.asarray(ge0),
                               rtol=1e-5, atol=1e-7)
    text = _lowered_grad(hidden, emb, targets, 8)
    assert "tensor<2x8x21xf32>" in text
    assert f"tensor<2x{s}x21xf32>" not in text


def test_fused_ce_gradient_program_makes_three_products_in_one_loop():
    """The logits once, then the two gradient products beside them, in the
    one loop over the chunks (a remat'd scan body under autodiff made four
    products in two loops), all three under the ``lm_head`` scope."""
    text = _lowered_grad(*_case(8, 2, 32, 8, 19), 8)
    assert text.count("stablehlo.dot_general") == 3
    assert text.count("stablehlo.while") == 1
    named = jax.jit(jax.grad(_fused_under_a_scope, argnums=(0, 1))).lower(
        *_case(8, 2, 32, 8, 19)).as_text(debug_info=True)
    products = [line for line in named.splitlines()
                if line.startswith("#loc") and "dot_general" in line]
    assert len(products) == 3
    assert all("lm_head/" in line for line in products)


def _fused_under_a_scope(h, e, t):
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    with jax.named_scope("model"):
        return softmax_cross_entropy_fused(h, e, t, chunk=8)


def test_fused_ce_plain_call_makes_one_product_and_no_gradient():
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    hidden, emb, targets = _case(9, 2, 32, 8, 19)
    text = jax.jit(lambda h, e: softmax_cross_entropy_fused(
        h, e, targets, chunk=8)).lower(hidden, emb).as_text()
    assert text.count("stablehlo.dot_general") == 1
    assert text.count("stablehlo.while") == 1
    # the loop stacks no ``[n_chunks, batch, chunk, d_model]`` gradient
    assert "dynamic_update_slice" not in text
    assert "dynamic_update_slice" in _lowered_grad(hidden, emb, targets, 8)


def test_fused_ce_forward_mode_is_refused():
    """What the form gives up: ``jax.jvp`` through the loss (and so
    forward-over-reverse second derivatives)."""
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    hidden, emb, targets = _case(10, 1, 8, 4, 7)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda h: softmax_cross_entropy_fused(
            h, emb, targets, chunk=4), (hidden,), (hidden,))


@pytest.mark.parametrize("form", ["value", "value_and_grads"])
def test_fused_ce_counts_the_form_each_trace_took(form):
    from horovod_tpu import metrics
    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    def count(of):
        m = metrics.registry().get("hvt_loss_chunks_traced_total")
        return m.labels(chunk="8", vocab="23", form=of).value if m else 0.0

    hidden, emb, targets = _case(11, 2, 30, 8, 23)
    other = "value" if form == "value_and_grads" else "value_and_grads"
    before = count(form), count(other)

    def loss(h, e):
        return softmax_cross_entropy_fused(h, e, targets, chunk=8)

    traced = jax.grad(loss) if form == "value_and_grads" else loss
    jax.jit(traced).lower(hidden, emb)
    # four chunks of 8 cover 30 positions, and only the form taken counts
    assert (count(form), count(other)) == (before[0] + 4, before[1])


def test_fused_ce_vocab_sharded_matrix():
    """A matrix sharded over its vocabulary rows (what
    ``param_partition_spec`` gives a tensor-parallel head): the rule's
    products are plain ``dot_general``s that GSPMD partitions; value and
    gradients are the unsharded ones and the matrix's gradient keeps the
    matrix's sharding."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops.losses import softmax_cross_entropy_fused

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("tp",))
    hidden, emb, targets = _case(12, 2, 24, 8, 32)
    rows = NamedSharding(mesh, P("tp", None))
    sharded = jax.device_put(emb, rows)

    def loss(h, e):
        return softmax_cross_entropy_fused(h, e, targets, chunk=8)

    l0, (gh0, ge0) = jax.value_and_grad(_naive, argnums=(0, 1))(
        hidden, emb, targets)
    l1, (gh1, ge1) = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1)),
        out_shardings=(None, (None, rows)))(hidden, sharded)
    assert ge1.sharding.is_equivalent_to(rows, 2)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh0),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(ge1), np.asarray(ge0),
                               rtol=1e-5, atol=1e-7)
