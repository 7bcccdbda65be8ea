"""Memory-bounded LM losses.

``softmax_cross_entropy_fused`` computes the language-model loss straight
from hidden states and the (tied) embedding matrix WITHOUT materializing
the full ``[batch, seq, vocab]`` logits tensor: the sequence axis is
processed in chunks under one ``lax.scan``, so peak activation memory is
``[batch, chunk, vocab]`` whether or not the loss is differentiated.

Under differentiation each chunk's logits are made ONCE (PR 42). The
loss is the last operation of a forward pass, so ``d loss / d logits =
(softmax - onehot) w / N`` is known while the chunk's logits are still
in hand, and the pass that makes the logits also makes the two gradient
products: three ``[T, d] x [d, V]`` products a step, where a remat'd
scan body under autodiff made four (the logits a second time in the
backward loop). What is kept for the backward pass is the hidden
states' gradient (``[batch, seq, d_model]`` in ``hidden``'s dtype) and
the matrix's float32 gradient (``[vocab, d_model]``); the backward rule
only scales them by the incoming cotangent. Nothing ``vocab`` wide
outlives its chunk. The price: the loss is differentiable once, in
reverse mode. Forward mode (``jax.jvp``, ``jax.jacfwd``, and with them
``jax.hessian``) is refused by ``jax.custom_vjp``; a second reverse pass
would differentiate the gradient pass as written and keep every chunk's
``vocab``-wide residuals, so second derivatives are given up with it.

Why it matters on TPU: at vocab 32k, seq 1k, bs 8 the logits tensor is
~1 GB of fp32 HBM that exists only to be softmaxed once — the classic
memory-bound tail of an LM step. Bounding it frees HBM for larger
per-chip batches (the lever that raises MFU). No reference counterpart
(the reference ships no model/loss code).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _count_trace(chunk, vocab, form, n_chunks):
    """The engagement counter: the chunks of one traced loss, by the form
    the trace took. Trace-time Python only."""
    try:
        from horovod_tpu import metrics

        metrics.counter(
            "hvt_loss_chunks_traced_total",
            "chunks of the chunked cross-entropy traced into compiled "
            "programs, by form: the value alone, or the value with both "
            "gradients made beside it, each `_weighted` where the caller "
            "gave a weight a position (counted per trace, not per "
            "execution)",
            ("chunk", "vocab", "form"),
        ).labels(chunk=str(chunk), vocab=str(vocab), form=form
                 ).inc(n_chunks)
    except Exception:
        pass  # telemetry must never break a trace


def _walk(hidden, emb, targets, weights, chunk, with_grads):
    """One pass over the chunks: the mean loss and, ``with_grads``, its
    gradients for ``hidden`` and ``emb`` (else ``None, None``);
    ``weights`` None, or a position's weight in the sum, which is divided
    by the number of positions whatever the weights add up to."""
    b, s, d = hidden.shape
    vocab = emb.shape[0]
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    # 1 for real tokens, 0 for padding — padded positions contribute 0
    # to the sum (and to both gradients) regardless of their (garbage)
    # logits
    if weights is None:
        mask = (jnp.arange(s + pad) < s).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, (b, s + pad))
    else:
        # the padding's weight is 0, as its mask was
        mask = jnp.pad(weights.astype(jnp.float32), ((0, 0), (0, pad)))
    n_chunks = (s + pad) // chunk
    _count_trace(chunk, vocab,
                 ("value_and_grads" if with_grads else "value")
                 + "_weighted" * (weights is not None), n_chunks)

    # [n_chunks, B, chunk, ...] scan layout
    hs = jnp.moveaxis(hidden.reshape(b, n_chunks, chunk, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(b, n_chunks, chunk), 1, 0)
    ms = jnp.moveaxis(mask.reshape(b, n_chunks, chunk), 1, 0)

    def body(carry, xs):
        total, demb = carry
        h, t, w = xs
        if with_grads:
            # the chunk as a buffer of its own, as ``jax.checkpoint`` gave
            # the old backward loop: with the slice out of ``hs`` fused
            # into it, the matrix's gradient product is tiled a third
            # slower (18.3 against 13.1 ms a step at 16,382 x 4096 x
            # 16,384 on a v5e, PR 42)
            h = lax.optimization_barrier(h)
        h32, emb32 = h.astype(jnp.float32), emb.astype(jnp.float32)
        # the name models.GPT gives its own vocabulary projection
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bcd,vd->bcv", h32, emb32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        total = total + ((lse - tgt) * w).sum()
        if not with_grads:
            return (total, demb), None
        onehot = t[..., None] == jnp.arange(vocab, dtype=t.dtype)
        dlogits = (jnp.exp(logits - lse[..., None]) - onehot) * (
            w / (b * s))[..., None]
        # made once and read by both products; left to the compiler each
        # product makes ``exp`` again for every tile of its own. The same
        # float32 values reach the same products (the chip's gradients
        # keep their bits), and it lives no longer than the logits do.
        dlogits = lax.optimization_barrier(dlogits)
        with jax.named_scope("lm_head"):
            dh = jnp.einsum("bcv,vd->bcd", dlogits, emb32)
            demb = demb + jnp.einsum("bcv,bcd->vd", dlogits, h32)
        return (total, demb), dh.astype(h.dtype)

    demb = jnp.zeros((vocab, d), jnp.float32) if with_grads else None
    (total, demb), dhs = lax.scan(body, (jnp.float32(0.0), demb),
                                  (hs, ts, ms))
    loss = total / (b * s)
    if not with_grads:
        return loss, None, None
    dhidden = jnp.moveaxis(dhs, 0, 1).reshape(b, s + pad, d)[:, :s]
    return loss, dhidden, demb.astype(emb.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_loss(hidden, emb, targets, weights, chunk):
    return _walk(hidden, emb, targets, weights, chunk, with_grads=False)[0]


def _chunked_loss_fwd(hidden, emb, targets, weights, chunk):
    loss, dhidden, demb = _walk(hidden, emb, targets, weights, chunk,
                                with_grads=True)
    # (the weights are data: no gradient is made for them, and a zero of
    # their shape is what the rule hands back)
    return loss, (dhidden, demb, weights)


def _chunked_loss_bwd(chunk, grads, g):
    dhidden, demb, weights = grads
    return ((g * dhidden).astype(dhidden.dtype),
            (g * demb).astype(demb.dtype), None,
            None if weights is None else jnp.zeros_like(weights))


_chunked_loss.defvjp(_chunked_loss_fwd, _chunked_loss_bwd)


def softmax_cross_entropy_fused(hidden, emb, targets, *, chunk=128,
                                weights=None):
    """Mean token cross-entropy of ``hidden @ emb.T`` against ``targets``.

    Args:
      hidden: [batch, seq, d_model] final hidden states (any float dtype;
        the projection accumulates in fp32).
      emb: [vocab, d_model] output/tied embedding matrix.
      targets: [batch, seq] int target ids.
      chunk: sequence-chunk length; peak logits memory is
        [batch, chunk, vocab]. Sequences that are not a chunk multiple
        are zero-padded and masked — the chunk size (and therefore the
        memory bound and MXU tile shape) is honored for ANY seq.
      weights: None, or [batch, seq] floats: position ``i``'s
        cross-entropy times ``weights[i]`` in the sum, **which is divided
        by the number of positions, batch x seq, and not by the weights'
        sum** (a block-diffusion loss: ``m_i / t`` over the masked
        positions, 0 elsewhere, ``models.diffusion.noise_blocks``). Data:
        no gradient flows to them. ``None`` traces what a call without the
        argument traced.

    Returns the scalar mean loss over all tokens. Differentiable once, in
    reverse mode, w.r.t. ``hidden`` and ``emb``; gradients match the
    unchunked computation. They are made beside the value, in the pass
    that makes each chunk's logits (a ``jax.custom_vjp``), and kept for
    the backward pass: ``[batch, seq, d_model]`` in ``hidden``'s dtype and
    ``[vocab, d_model]`` in float32. Forward-mode differentiation through
    the loss raises, and second derivatives are given up (a second reverse
    pass would store every chunk's logits). Called without differentiation
    it makes the value alone and no gradient product.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if weights is not None and weights.shape != targets.shape:
        raise ValueError(
            f"weights {weights.shape} are one a position, as targets "
            f"{targets.shape}")
    return _chunked_loss(hidden, emb, targets, weights,
                         min(chunk, hidden.shape[1]))
