"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434; the
attention of DeepSeek-V3 and of the models built on its ``config.json``,
Kanana-2 among them): keys and values come out of one narrow **latent** a
position, the query-key width is two parts from different projections,
one of them rotated, and **the rotated key is one vector a position that
every head shares**. A head's queries and keys are then wider than its
values (192 on 128 at the published sizes).

For an input ``x [batch, seq, d]``, ``H`` heads, a latent of ``r``
channels, an unrotated query-key part of ``n`` channels a head, a rotated
one of ``e`` and values of ``v`` channels, the layer is six steps, each
under a ``jax.named_scope`` of its name so that a device trace can be split
by them:

1. ``mla_q_proj``: ``q = x W_q``, ``W_q [d, H, n + e]``, a head's columns
   ``q_n | q_r``; no bias, no query latent (``q_lora_rank`` null). Two
   products, ``q_n = x W_q[.., :n]`` and ``q_r = x W_q[.., n:]`` (its
   columns regrouped: step 4): the
   *weight's* columns are cut (a few megabytes a layer), so that no
   activation is ever sliced, and none put beside another, for step 5.
2. ``mla_kv_down``: ``[c | k_r] = x W_kva``, ``W_kva [d, r + e]``; ``c <-
   RMSNorm_r(c)`` in float32 with a weight ``[r]``. ``k_r [b, s, e]`` has
   no head axis.
3. ``mla_kv_up``: ``[k_n | v] = c W_kvb``, ``W_kvb [r, H, n + v]``, again
   two products over the weight's two groups of columns.
4. ``mla_rope``: the rotary at ``rotary_base`` over the ``e`` channels of
   ``q_r`` (every head) and of ``k_r``. The parameters hold the rotated
   columns in the published order, interleaved pairs ``(2j, 2j + 1)`` at
   angle ``t base^(-2j / e)`` (``rope_interleave``); the *weights'* columns
   are regrouped to halves (``pairs_to_halves``: a fixed permutation of
   ``e`` columns of ``W_q`` a head and of ``W_kva``, a few megabytes a
   layer) and the activations rotated half against half, which a TPU does
   with two slices where pairs are a shuffle across lanes. ``q_r . k_r``
   sums the same ``e`` products in another order. **A model whose latent
   attention carries no position** (``rotary`` false: Kimi Linear's
   ``mla_use_nope``, where the delta-rule layers carry the order) has the
   same ``e`` channels of ``q_r`` and the one shared ``k_r`` and leaves
   this step and the weights' regrouping out: step 5 takes the four parts
   as the projections left them, the same kernel call shape for shape.
5. ``mla_core``: ``score_h(t, u) = (q_n,h(t) . k_n,h(u) + q_r,h(t) .
   k_r(u)) (n + e)^-1/2``, causal softmax in float32, ``o_h = sum_u p
   v_h(u)`` (``causal_attention``). Where ``resolve_flash`` says so (the
   rule every attention of the package goes by: the shape decides) the
   products over positions are ``ops/flash_attention.py``'s kernels, **and
   they take the four parts as the projections and the rotary left them**:
   the score's two products are summed inside the kernel, every head's
   grid steps read the one ``k_r`` a position, and nothing ``n + e`` wide
   exists in either pass (``k_r``'s gradient is the heads' sum of what
   the kernel gives a head). Everywhere else (the CPU, a short or odd
   length) the query and the key are assembled whole, ``q_h = q_n,h |
   q_r,h`` and ``k_h = k_n,h | k_r`` with ``k_r`` broadcast over the heads
   (``whole_key``), for einsums with a float32 softmax: the plain body
   the kernels are compared with.
6. ``mla_out_proj``: ``out = [o_1 .. o_H] W_o``, ``W_o [H, v, d]``.

For a caller that asks for the collection ``intermediates`` the mixer's
own input and output are sown there (``mla_input``, ``mla_output``), for a
comparison with a plain reference on the same input.
"""

from __future__ import annotations

from typing import Any, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import flash_attention as flash
from horovod_tpu.ops.rotary import rotary


def _count_trace(heads, rank, nope, rope, value):
    """One count a traced layer."""
    _pallas.count_trace(
        "hvt_mla_layers_traced_total",
        "latent-attention layers traced into compiled programs (counted "
        "per trace, not per execution)",
        heads=heads, rank=rank, nope=nope, rope=rope, value=value)


def pairs_to_halves(w, width: int):
    """The last ``width`` columns of ``w``'s last axis from interleaved
    pairs to halves: ``(x0, y0, x1, y1, ..) -> (x0, x1, .. | y0, y1,
    ..)``; the columns before them as they are."""
    keep = w.shape[-1] - width
    return jnp.concatenate(
        [w[..., :keep], w[..., keep::2], w[..., keep + 1::2]], axis=-1)


def latent_norm(c, weight, eps: float):
    """RMSNorm over the latent's channels in float32, ``c.dtype`` out."""
    c32 = c.astype(jnp.float32)
    return (c32 * jax.lax.rsqrt(jnp.mean(c32 * c32, axis=-1, keepdims=True)
                                + eps) * weight).astype(c.dtype)


def whole_key(k_n, k_r):
    """A head's key ``k_n,h | k_r``: the unrotated part ``k_n [b, s, H,
    n]`` beside the one rotated key a position ``k_r [b, s, e]``, which
    every head reads."""
    return jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None, :],
                               k_n.shape[:-1] + k_r.shape[-1:])], axis=-1)


def score_scale(nope: int, rope: int) -> float:
    """``(n + e)^-1/2``: the whole query-key width's."""
    return 1.0 / np.sqrt(nope + rope)


def causal_attention(q_n, q_r, k_n, k_r, v, positions, scale, use_flash):
    """``softmax((q_n k_n^T + q_r k_r^T) scale) v`` over the positions
    before and at a query's: ``q_n`` and ``k_n [b, s, H, n]``, ``q_r [b, s,
    H, e]``, the shared ``k_r [b, s, e]`` and ``v [b, s, H, v]``. The
    flash kernels on the parts as they are where ``resolve_flash`` says
    so; else the query and the key whole, for einsums with a float32
    softmax."""
    if flash.resolve_flash(use_flash, q_n.shape[1]):
        return flash.flash_attention(q_n, k_n, v, q_r=q_r, k_r=k_r,
                                     causal=True, scale=scale)
    q, k = jnp.concatenate([q_n, q_r], axis=-1), whole_key(k_n, k_r)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    seen = positions[:, None, :] <= positions[:, :, None]      # [b, q, k]
    scores = jnp.where(seen[:, None], scores * scale, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class LatentAttention(nn.Module):
    """The mixer. Parameters: ``q_proj [d, H, n + e]``, ``kv_down [d, r +
    e]``, ``kv_norm [r]`` (from one), ``kv_up [r, H, n + v]``, ``o_proj
    [H, v, d]``; the rotated columns of ``q_proj`` (a head's last ``e``)
    and of ``kv_down`` (the last ``e``) in the published interleaved
    order."""

    heads: int
    rank: int
    nope: int = 128
    rope: int = 64
    value: int = 128
    rotary_base: float = 10000.0
    norm_eps: float = 1e-6
    use_flash: Union[bool, str] = False
    dtype: Any = jnp.bfloat16
    rotary: bool = True     # False: q_r and k_r are not turned (step 4)

    @nn.compact
    def __call__(self, x, positions):
        d, seq = x.shape[-1], x.shape[-2]
        h, r, n, e, v = (self.heads, self.rank, self.nope, self.rope,
                         self.value)
        if r <= 0 or e % 2:
            raise ValueError(
                f"latent attention needs a latent rank (got {r}) and an "
                f"even rotated width (got {e})")
        init = nn.initializers.normal(0.02)
        w_q = self.param("q_proj", init, (d, h, n + e))
        w_kva = self.param("kv_down", init, (d, r + e))
        kv_norm = self.param("kv_norm", nn.initializers.ones_init(), (r,))
        w_kvb = self.param("kv_up", init, (r, h, n + v))
        w_o = self.param("o_proj", init, (h, v, d))
        _count_trace(h, r, n, e, v)

        self.sow("intermediates", "mla_input", x)
        lead = x.shape[:-2]
        x = x.reshape(-1, seq, d).astype(self.dtype)
        positions = jnp.broadcast_to(positions, lead + (seq,)).reshape(
            -1, seq)
        # the weights' columns are cut, never an activation's: a head's
        # unrotated and rotated query columns, its key's and its value's
        by_head = lambda t, w: jnp.einsum("bsd,dhk->bshk", t, w)
        regroup = pairs_to_halves if self.rotary else (lambda w, _: w)
        with jax.named_scope("mla_q_proj"):
            w_q = w_q.astype(self.dtype)
            q_n = by_head(x, w_q[..., :n])
            q_r = by_head(x, regroup(w_q[..., n:], e))
        with jax.named_scope("mla_kv_down"):
            down = jnp.dot(x, regroup(w_kva.astype(self.dtype), e))
            c, k_r = down[..., :r], down[..., r:]
            c = latent_norm(c, kv_norm, self.norm_eps)
        with jax.named_scope("mla_kv_up"):
            w_kvb = w_kvb.astype(self.dtype)
            k_n = by_head(c, w_kvb[..., :n])
            values = by_head(c, w_kvb[..., n:])
        if self.rotary:
            with jax.named_scope("mla_rope"):
                q_r, k_r = rotary((q_r, k_r), positions, self.rotary_base)
        with jax.named_scope("mla_core"):
            out = causal_attention(q_n, q_r, k_n, k_r, values, positions,
                                   score_scale(n, e), self.use_flash)
        with jax.named_scope("mla_out_proj"):
            out = jnp.einsum("bshv,hvd->bsd", out, w_o.astype(self.dtype))
        out = out.reshape(*lead, seq, d)
        self.sow("intermediates", "mla_output", out)
        return out


def mla_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``LatentAttention``: the heads are
    the tensor-parallel dimension. ``q_proj`` and ``kv_up`` are
    column-parallel by head, ``o_proj`` row-parallel (one sum a layer);
    the down-projection, whose rotated key every head reads, and the
    latent's norm replicate."""
    return {"q_proj": P(None, tp_axis, None),
            "kv_up": P(None, tp_axis, None),
            "o_proj": P(tp_axis, None, None)}.get(name, P())
