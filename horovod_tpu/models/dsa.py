"""Grouped-query attention over the keys a learned indexer chooses
(DeepSeek's published sparse attention, arXiv:2512.02556 section 2.1; the
attention of the models that carry its ``sa_config``, Keye-VL-2.0 among
them): a second, small attention-like module scores every causal key for
every query, **each query attends the ``topk`` keys it scores highest**,
and the indexer is trained by a loss of its own, since the choice has no
gradient.

For an input ``x [batch, seq, d]``, ``H`` query heads on ``H_kv``
key-value heads of ``head_dim``, ``J`` index heads of ``e`` channels on
**one index key a position**, the layer is six steps, each under a
``jax.named_scope`` of its name so that a device trace can be split by
them:

1. ``dsa_proj``: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; ``q`` and
   ``k`` RMS-normalised a head in float32 (one weight ``[head_dim]`` for
   all query heads, one for all key heads); the rotary at ``rotary_base``
   over the halves of a head.
2. ``dsa_index``: on ``x~ = stop_gradient(x)``: ``q^I = x~ W_qI`` (``[d,
   J, e]``), ``k^I = LayerNorm_e(x~ W_kI)`` in float32 (``index_k_norm [2,
   e]``: the scale's row, then the bias's), the rotary at the same base
   over the halves of ``e`` on both, ``w = x~ W_w J^-1/2 e^-1/2`` (``[d,
   J]``, float32 out), and the scores ``I(t, u) = sum_j w_j(t)
   relu(q^I_j(t) . k^I(u))`` for ``u <= t``, **made and compared in
   float32** (``ops/dsa.py``, ``index_scores``). The three parts are one
   function of the indexer's four leaves, taken with its pullback
   (``jax.vjp``) for step 5.
3. ``dsa_select``: ``S_t``, every ``u <= t`` while ``t < topk`` and from
   there on the ``topk`` causal keys of the largest ``I(t, u)``, equal
   scores to the lower ``u``; handed on as a mask ``[b, s, s]`` int8 made
   once a layer and read by every head (``choose``), and beside it ``I``'s
   log-sum-exp over ``S_t`` ``[b, s, 1]`` for step 5, made in the same
   pass over the scores. Under ``remat`` the mask (``KEPT_CHOICE``: the
   choice has no gradient, so it is not made twice) and step 5's four
   gradients (``KEPT_INDEX_GRADS``) are kept for the backward pass and
   nothing else of steps 2, 3 and 5, **which therefore run once a layer
   and step**: the recomputed layer holds no indexer, no index scores, no
   choice and no loss.
4. ``dsa_core``: ``score_h(t, u) = q_h(t) . k_{h // g}(u) head_dim^-1/2``
   **for ``u`` in ``S_t`` only**, softmax in float32 over ``S_t``, ``o_h =
   sum p v``. Where ``resolve_flash`` says so, ``ops/flash_attention.py``'s
   kernels with the mask as their ``choice``; everywhere else einsums with
   a float32 softmax.
5. ``dsa_target``: the indexer's loss ``L_I = mean_t sum_{u in S_t} pbar
   (log pbar - log r)``, ``pbar`` the main attention's probabilities
   averaged over the heads (detached), ``r`` the softmax over ``S_t`` of
   ``I`` (``index_loss``, from the indexer's parts and step 3's
   log-sum-exp). ``L_I`` reaches the indexer's four leaves and
   nothing else, on a detached input, so its whole gradient is known here:
   the loss is made **with its gradients by the three parts** (one kernel
   call, or ``jax.value_and_grad`` of the plain body), step 2's pullback
   takes them to the four leaves at once (float32 in the leaves' shapes:
   2,261,120 numbers, 9 MB a layer at 2,048 wide, 16 index heads of 64),
   those carry the name ``KEPT_INDEX_GRADS``, and ``ops.with_gradient``
   ties them to the value: the backward pass scales them by the
   cotangent. (They are added onto zeros made as the layer begins, and the
   layer's output waits for them: two of ``jax.lax.optimization_barrier``
   that change no number and decide where the compiled step keeps the four;
   the reason is beside them.) The language-model loss reaches everything
   but the four.
6. ``dsa_out_proj``: ``out = [o_1 .. o_H] W_o``.

The mixer returns ``(out, L_I)``; ``models.GPT`` hands the layers' sum on
under ``"dsa_index"`` of its auxiliary losses (``return_aux``), and the
training script adds it to its loss. For a caller that asks for the
collection ``intermediates`` the mixer's own input, output, choice and
loss are sown there (``dsa_input``, ``dsa_output``, ``dsa_choice``,
``dsa_index_loss``) and the float32 index scores of a sequence's last 128
queries (``dsa_scores_tail``), from which a caller can see that the
choice is exactly their ``top_k`` and that they are float32.
"""

from __future__ import annotations

from typing import Any, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

# a head's norm is the latent's: RMSNorm over the last axis in float32
from horovod_tpu.models.mla import latent_norm as head_rms
from horovod_tpu.ops import _pallas
from horovod_tpu.ops import dsa as ops
from horovod_tpu.ops import flash_attention as flash
from horovod_tpu.ops.rotary import rotary

# The name (``jax.ad_checkpoint.checkpoint_name``) of the choice: a byte a
# query and key, kept by ``models.GPT``'s ``remat`` policy.
KEPT_CHOICE = "dsa_choice"
# and of ``L_I``'s gradients by the indexer's four leaves, float32 in the
# leaves' shapes (``d (J e + e + J) + 2 e`` numbers a layer), kept likewise.
KEPT_INDEX_GRADS = "dsa_index_grads"
# Rows of the index scores (a sequence's last) sown beside the choice.
SOWN_ROWS = 128


def _count_trace(heads, kv_heads, head_dim, index_heads, index_dim, topk):
    """One count a traced layer."""
    _pallas.count_trace(
        "hvt_dsa_layers_traced_total",
        "sparse-attention layers (keys chosen by a learned indexer) traced "
        "into compiled programs (counted per trace, not per execution)",
        heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        index_heads=index_heads, index_dim=index_dim, topk=topk)


def index_key_norm(k, scale_and_bias, eps: float):
    """LayerNorm over the index key's channels, float32 in and out."""
    k = k.astype(jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    return ((k - mean) * jax.lax.rsqrt(var + eps) * scale_and_bias[0]
            + scale_and_bias[1])


def chosen_attention(q, k, v, choice, scale, kernels: bool):
    """``(o [b, s, H, d], lse [b, s, H])`` of ``softmax(q k^T scale)`` over
    the keys ``choice [b, s, s]`` marks: the flash kernels, or einsums with
    a float32 softmax."""
    if kernels:
        return flash.flash_attention_with_lse(q, k, v, choice=choice,
                                              causal=True, scale=scale)
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where((choice != 0)[:, None], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None]).astype(v.dtype)
    return (jnp.einsum("bhqk,bkhd->bqhd", probs, v),
            jnp.transpose(lse, (0, 2, 1)))


class SparseAttention(nn.Module):
    """The mixer. Parameters: ``q_proj [d, H, hd]``, ``k_proj`` and
    ``v_proj [d, H_kv, hd]``, ``q_norm`` and ``k_norm [hd]`` (from one),
    ``o_proj [H, hd, d]``; the indexer's four: ``index_q [d, J, e]``,
    ``index_k [d, e]``, ``index_k_norm [2, e]`` (a row of ones, a row of
    zeros) and ``index_w [d, J]``."""

    heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int = 64
    topk: int = 2048
    rotary_base: float = 10000.0
    norm_eps: float = 1e-6
    use_flash: Union[bool, str] = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, positions):
        d, seq = x.shape[-1], x.shape[-2]
        h, h_kv, hd = self.heads, self.kv_heads, self.head_dim
        j, e = self.index_heads, self.index_dim
        if h % h_kv or j <= 0 or hd % 2 or e % 2 or self.topk <= 0:
            raise ValueError(
                f"sparse attention needs query heads ({h}) in whole groups "
                f"over the key-value heads ({h_kv}), index heads (got {j}), "
                f"even widths (got {hd}, {e}) and a topk (got {self.topk})")
        init = nn.initializers.normal(0.02)
        ones = nn.initializers.ones_init()
        w_q = self.param("q_proj", init, (d, h, hd))
        w_k = self.param("k_proj", init, (d, h_kv, hd))
        w_v = self.param("v_proj", init, (d, h_kv, hd))
        q_norm = self.param("q_norm", ones, (hd,))
        k_norm = self.param("k_norm", ones, (hd,))
        w_o = self.param("o_proj", init, (h, hd, d))
        w_qi = self.param("index_q", init, (d, j, e))
        w_ki = self.param("index_k", init, (d, e))
        ki_norm = self.param(
            "index_k_norm", lambda key, shape: jnp.stack(
                [jnp.ones(shape[1:]), jnp.zeros(shape[1:])]), (2, e))
        w_w = self.param("index_w", init, (d, j))
        indexer = (w_qi, w_ki, ki_norm, w_w)
        _count_trace(h, h_kv, hd, j, e, self.topk)
        kernels = flash.resolve_flash(self.use_flash, seq)

        self.sow("intermediates", "dsa_input", x)
        lead = x.shape[:-2]
        x = x.reshape(-1, seq, d).astype(self.dtype)
        positions = jnp.broadcast_to(positions, lead + (seq,)).reshape(
            -1, seq)
        # where step 5's four gradients will be kept, made as the layer
        # begins (see there)
        x, held = jax.lax.optimization_barrier(
            (x, jax.tree.map(jnp.zeros_like, indexer)))
        by_head = lambda t, w: jnp.einsum("bsd,dhk->bshk", t,
                                          w.astype(self.dtype))
        scale = 1.0 / np.sqrt(hd)
        with jax.named_scope("dsa_proj"):
            q, k, v = by_head(x, w_q), by_head(x, w_k), by_head(x, w_v)
            q, k = rotary((head_rms(q, q_norm, self.norm_eps),
                           head_rms(k, k_norm, self.norm_eps)), positions,
                          self.rotary_base)
        detached = jax.lax.stop_gradient(x)

        def index_parts(w_qi, w_ki, ki_norm, w_w):
            """``q^I``, ``k^I`` and ``w`` of the detached input."""
            q_i = rotary(
                by_head(detached, w_qi).astype(jnp.float32), positions,
                self.rotary_base).astype(self.dtype)
            k_i = rotary(index_key_norm(
                jnp.dot(detached, w_ki.astype(self.dtype)), ki_norm,
                self.norm_eps), positions, self.rotary_base).astype(
                    self.dtype)
            w = jnp.dot(detached, w_w.astype(self.dtype),
                        preferred_element_type=jnp.float32) / np.sqrt(j * e)
            return q_i, k_i, w

        with jax.named_scope("dsa_index"):
            # of detached leaves: neither the parts nor what their
            # pullback returns carries a derivative, so no pass
            # differentiates the pullback again
            parts, to_leaves = jax.vjp(
                index_parts, *jax.lax.stop_gradient(indexer))
            scores = (ops.index_scores if kernels
                      else ops.index_scores_plain)(*parts)
        with jax.named_scope("dsa_select"):
            choice, lse_i = (ops.choose if kernels else ops.choose_plain)(
                scores, self.topk)
            choice = checkpoint_name(choice, KEPT_CHOICE)
            if kernels:
                # Placement, not arithmetic. With nothing of XLA's own
                # between the choice and the loss, the compiled step of
                # the published size takes 12.538 GiB where the parent's
                # took 11.591 (compiled for a described v5e: the same
                # kernels in the same order, other fusions around the
                # experts and more of their buffers alive at once); with a
                # ``logsumexp`` of XLA's in that place, as the parent had
                # over the whole ``[s, s]``, it takes 11.523, over 128
                # columns as well as over all (8 MiB read a layer where
                # the parent's two passes read 2.5 GiB). What else was
                # compiled, and that why is open: PERF.md, PR 65.
                lse_i = lse_i + 0.0 * jax.nn.logsumexp(
                    scores[..., :ops.LANES], axis=-1, keepdims=True)
        with jax.named_scope("dsa_core"):
            out, lse = chosen_attention(q, k, v, choice, scale, kernels)
        with jax.named_scope("dsa_target"):
            if kernels:
                kl, by_parts = ops.index_loss(q, k, lse, *parts, lse_i,
                                              choice, scale)
            else:
                kl, by_parts = jax.value_and_grad(
                    ops.index_loss_plain, argnums=(3, 4, 5))(
                        q, k, lse, *parts, choice, scale)
            _pallas.count_trace(
                "hvt_dsa_index_grads_kept_total",
                "sparse-attention layers traced whose indexer gradients "
                "are made in the first pass and named for remat (counted "
                "per trace, not per execution)")
            # Nothing reads the four before the backward pass, and left to
            # itself XLA's scheduler puts every layer's pullback off until
            # then with its operands alive meanwhile; made on time but
            # written wherever the heap has room between the layer's large
            # buffers, eight layers' small long-lived arrays cut it up. So
            # they are added onto zeros that exist from the layer's first
            # operation, and the layer's output waits for them: the
            # compiled step of the published size takes 0.52 GiB less than
            # with the loss made twice, where either half alone takes 0.5
            # to 2.3 GiB more (PERF.md, PR 52).
            kept = checkpoint_name(jax.tree.map(
                jnp.add, held, to_leaves(by_parts)), KEPT_INDEX_GRADS)
            out, kept = jax.lax.optimization_barrier((out, kept))
            index_loss = ops.with_gradient(kl, indexer, kept)
        with jax.named_scope("dsa_out_proj"):
            out = jnp.einsum("bshv,hvd->bsd", out, w_o.astype(self.dtype))
        out = out.reshape(*lead, seq, d)
        for name, value in (("dsa_output", out), ("dsa_choice", choice),
                            ("dsa_index_loss", index_loss),
                            ("dsa_scores_tail", scores[:, -SOWN_ROWS:])):
            self.sow("intermediates", name, value)
        return out, index_loss


def dsa_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``SparseAttention``: the heads are
    the tensor-parallel dimension. ``q_proj``, ``k_proj`` and ``v_proj``
    are column-parallel by head, ``o_proj`` row-parallel (one sum a
    layer); the heads' norms and **the indexer whole** on every chip:
    every chip must make the same choice."""
    return {"q_proj": P(None, tp_axis, None),
            "k_proj": P(None, tp_axis, None),
            "v_proj": P(None, tp_axis, None),
            "o_proj": P(tp_axis, None, None)}.get(name, P())
