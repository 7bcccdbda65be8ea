"""The chunked delta rule of ``models/kda.py`` (Kimi Delta Attention,
arXiv:2510.26692): ``ops/gated_delta_rule.py``'s rule with **a decay a key
channel** in place of one a head, in plain ``jax.numpy``.

``channel_delta_rule`` is what the mixer calls. It has one body today,
``channel_delta_rule_plain``, which holds the equations and has
``jax.grad`` of itself for a backward pass. The scalar rule's three
kernels have no sibling yet (``ROADMAP.md`` R2), so this module has no
``serves`` either: it comes with the kernels it would choose.

With ``g_t [d_k]`` a head (``g <= 0``) the recurrence is ``S' =
Diag(exp(g_t)) S_{t-1}``, ``S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T``,
``o_t = S_t^T q_t``. With ``G_i`` the cumulative sum of ``g`` inside a
chunk (a vector over the channels), the scalar rule's chunked form holds
with every ``exp(G_i - G_j)`` moved **inside** the sum over channels:
``N_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` for ``j < i``, ``T =
(I + N)^-1`` (``unit_lower_inverse``, shared), ``u = T (beta v) - T (beta
k exp(G)) S``, ``o_i = (q_i exp(G_i)) S + sum_{j <= i} (sum_c q_ic k_jc
exp(G_ic - G_jc)) u_j``, and the chunk hands on ``Diag(exp(G_last)) S +
(k exp(G_last - G))^T u``.

**The contract both delta rules keep** (this module and
``ops/gated_delta_rule.py``, the rule with a decay a head, say it in the
same words):

- *What is float32.* The decays, their cumulative sums and exponents, a
  chunk's system, its inverse and that inverse's products are float32 at
  the caller's precision, and the state ``S`` is carried in float32
  (``state_dtype``, the mixer's ``STATE_DTYPE``); the other products run in
  the operands' dtype and accumulate in float32.
- *Which exponents are taken.* Only ``exp(G_i - G_j)`` for ``j <= i``,
  ``exp(G_i)`` and ``exp(G_last - G_i)``, ``G`` the cumulative sum of ``g
  <= 0`` inside a chunk: **every exponent is of a non-positive number**,
  so every factor lies in ``[0, 1]`` whatever the decay. No ``exp(-G)`` is
  ever formed (it overflows float32 inside one chunk at these families'
  decays).
- *Which shapes ``serves`` sends to kernels.* A TPU backend, the chunk
  ``CHUNK`` and heads in whole 128-lane tiles; everything else, and every
  shape of a rule whose kernels are not built, runs the plain
  ``jax.numpy`` body, which has ``jax.grad`` of itself.

Here the second point takes more than a mask: the obvious fold ``(k_i
exp(G_i)) . (k_j exp(-G_j))`` takes the exponent of a positive cumulative
sum (``A`` up to 16). So a pair ``(i, j)`` in different sub-blocks of
``SUB`` positions factors through a **reference row between them**, ``r``
with ``j < r <= i``: ``(k_i exp(G_i - G_r)) . (k_j exp(G_r - G_j))``, both
exponents non-positive, by halves: at each of ``log2(chunk / SUB)``
levels the rows in the second half of a block of ``2 h`` positions and the
columns in its first half take that half's first row as ``r``, one
product a level; a pair inside one sub-block takes the difference ``G_i -
G_j`` itself, channel by channel (``[SUB, SUB, d_k]`` float32 a
sub-block). And the third is empty today: no kernel is built and there is
no ``serves``, so every shape runs the plain body.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops.gated_delta_rule import unit_lower_inverse

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# The chunk the rule takes where the caller names none (the longest the
# sequence allows up to this), and the sub-block inside which a pair of
# positions takes its decay channel by channel. A chunk's cost in the
# plain body is HBM traffic, not products: the sub-block's [SUB, SUB, d_k]
# differences grow with SUB, the levels' folded operands with log2(CHUNK /
# SUB), the carry's steps with 1 / CHUNK. On a v5e at 2 x 8192, 32 heads of
# 128 x 128, bf16, a forward / a forward and backward call took 46.9 /
# 118.7 ms at 32 x 8, 50.1 / 130.3 at 64 x 8, 55.7 / 138.2 at 128 x 8, 47.1
# / 128.4 at 32 x 4, 49.1 / 127.9 at 64 x 4 and, before the cumulative sums
# were a product, 57.1 / 149.7 at 64 x 16 against 56.2 / 137.9 at 64 x 8
# (benchmarks/kda_rule.py; PERF.md section 6, PR 55).
CHUNK, SUB = 32, 8
# Heads of one sequence that pass at a time (all of fewer).
HEADS_A_PASS = 8
# The name (``jax.ad_checkpoint.checkpoint_name``) of the rule's result,
# for a caller that recomputes its forward pass and would keep it: every
# pass is made again for its own backward pass (``channel_delta_rule_plain``),
# so a recomputed layer that kept ``o`` (bf16, 134 MB a layer of 2 x 8192 x
# 32 x 128) runs no rule of its own. ``models.GPT`` keeps it under ``remat``.
KEPT_OUTPUT = "kda_rule_output"


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the rule uses for ``seq_len`` positions."""
    return max(1, min(chunk or CHUNK, seq_len))


def channel_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                       state_dtype=_F32, precision=_HIGHEST):
    """The delta rule with a decay a channel, chunked:
    ``channel_delta_rule_plain``'s arguments and result, by the one body
    there is."""
    return checkpoint_name(channel_delta_rule_plain(
        q, k, v, g, beta, chunk=chunk, state_dtype=state_dtype,
        precision=precision), KEPT_OUTPUT)


def _sub_block(chunk: int) -> int:
    """The sub-block of a chunk: the largest power of two up to ``SUB``
    that divides it."""
    return math.gcd(chunk, SUB)


def _levels(chunk: int, sub: int):
    """Half-lengths ``h`` of the levels that join sub-blocks of ``sub``
    into the chunk: blocks of ``2 h`` positions, ``h`` from ``sub`` up.
    A chunk that is not ``sub`` times a power of two ends in one level
    whose block is the whole chunk, its first half the largest such
    power."""
    h = sub
    while h < chunk:
        yield h
        h *= 2


def _exp_where(mask, x):
    """``exp(x)`` where ``mask`` and 0 elsewhere, with nothing infinite on
    the way and no gradient through what is masked (``x`` is non-positive
    wherever ``mask`` holds: the caller's promise, and what keeps every
    value in ``[0, 1]``)."""
    return jnp.where(mask, jnp.exp(jnp.where(mask, x, 0.0)), 0.0)


def _decayed_products(q, k, cum):
    """``sum_c x_ic k_jc exp(G_ic - G_jc)`` for ``j <= i`` (0 above the
    diagonal), ``x`` each of ``q`` and ``k``: ``q``, ``k`` and ``cum``
    (``G``, float32) ``[..., c, d]`` -> ``[2, ..., c, c]`` float32. Every
    exponent is of a non-positive number (the module's docstring)."""
    c, d = q.shape[-2:]
    dtype = k.dtype
    sub = _sub_block(c)
    rows = jnp.stack([q, k]).astype(_F32)           # [2, ..., c, d]
    k32 = k.astype(_F32)
    at = np.arange(c)

    # inside a sub-block: the differences themselves
    blocks = lambda t: t.reshape(*t.shape[:-2], c // sub, sub, d)
    g_b = blocks(cum)
    lag = g_b[..., :, None, :] - g_b[..., None, :, :]      # [.., n, s, s, d]
    seen = (np.arange(sub)[:, None] >= np.arange(sub)[None, :])[..., None]
    decay = _exp_where(seen, lag)
    inside = jnp.sum(blocks(rows)[..., :, None, :]
                     * blocks(k32)[..., None, :, :] * decay, axis=-1)
    # [2, .., n, s, s] onto the diagonal of [2, .., c, c]
    products = jnp.where(
        np.eye(c // sub, dtype=bool)[:, None, :, None],
        inside[..., :, :, None, :], 0.0).reshape(*inside.shape[:-3], c, c)

    # across sub-blocks, by halves: the first row of a block's second
    # half is the reference of its rows (from it down) and of the first
    # half's columns (up to it)
    for h in _levels(c, sub):
        start = at // (2 * h) * (2 * h)             # the block's first row
        second = at >= start + h
        if c % (2 * h):
            g_ref = jnp.take(cum, np.minimum(start + h, c - 1), axis=-2)
        else:
            g_ref = jnp.broadcast_to(
                cum.reshape(*cum.shape[:-2], -1, 2 * h, d)[..., h:h + 1, :],
                (*cum.shape[:-2], c // (2 * h), 2 * h, d)).reshape(cum.shape)
        fall = _exp_where(second[:, None], cum - g_ref)
        rise = _exp_where(~second[:, None], g_ref - cum)
        level = jnp.einsum("r...id,...jd->r...ij", (rows * fall).astype(dtype),
                           (k32 * rise).astype(dtype),
                           preferred_element_type=_F32)
        quarter = second[:, None] & ~second[None, :] & (
            start[:, None] == start[None, :])
        products = products + jnp.where(quarter, level, 0.0)
    return products


def channel_delta_rule_plain(q, k, v, g, beta, *,
                             chunk: Optional[int] = None, state_dtype=_F32,
                             precision=_HIGHEST):
    """The delta rule with a decay a channel, chunked, in plain
    ``jax.numpy``.

    ``q``, ``k`` ``[batch, s, H, d_k]`` (normalised and scaled by the
    caller), ``v [batch, s, H, d_v]``, ``g [batch, s, H, d_k]`` and ``beta
    [batch, s, H]`` float32 (``g <= 0``). Returns ``o [batch, s, H, d_v]``
    in ``v.dtype`` with ``S_t = Diag(exp(g_t)) S_{t-1} + k_t (beta_t (v_t
    - (Diag(exp(g_t)) S_{t-1})^T k_t))^T`` from ``S = 0`` and ``o_t =
    S_t^T q_t``: the module's docstring has the chunked form. A sequence
    the chunk does not divide is padded with positions whose ``g`` and
    ``beta`` are 0 (they decay nothing, write nothing and are cut off
    again). ``state_dtype`` is what the decays, their sums, the inverse
    and the carried state are computed in and ``precision`` that of the
    float32 products that invert the system: the caller's constants
    (``kda.STATE_DTYPE``, ``kda.INVERSE_PRECISION``).

    No head's state reads another's and no sequence's, so one sequence's
    ``HEADS_A_PASS`` heads pass at a time (``lax.map``) under
    ``jax.checkpoint``: the backward pass makes a pass's chunks again and
    holds the intermediates of that pass alone (a chunk's matrices and
    folded operands in float32 for all of 2 x 8192 x 32 heads are 4.3 GiB:
    PERF.md section 6, PR 55)."""
    batch, seq, heads, d_k = q.shape
    c = chunk_for(seq, chunk)
    pad = -seq % c
    if pad:
        grow = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    a_pass = math.gcd(heads, HEADS_A_PASS)
    # [sequence x pass, s, heads of the pass, ...]
    to_passes = lambda t: jnp.moveaxis(t.reshape(
        batch, seq + pad, heads // a_pass, a_pass, *t.shape[3:]), 2, 1
        ).reshape(-1, seq + pad, a_pass, *t.shape[3:])
    one = jax.checkpoint(lambda args: _one_pass(
        *args, chunk=c, state_dtype=state_dtype, precision=precision))
    o = jax.lax.map(one, tuple(to_passes(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o.reshape(batch, heads // a_pass, seq + pad, a_pass, -1),
                     1, 2)
    return o.reshape(batch, seq + pad, heads, -1)[:, :seq]


def _one_pass(q, k, v, g, beta, *, chunk, state_dtype, precision):
    """One sequence's pass of heads: ``q``, ``k``, ``g`` ``[s, h, d_k]``,
    ``v [s, h, d_v]``, ``beta [s, h]``, ``s`` a multiple of ``chunk`` ->
    ``o [s, h, d_v]``. The carry is a ``lax.scan`` over the chunks (the
    corrections of a chunk depend on the state that enters it); all else
    is batched over them."""
    seq, heads, d_k = q.shape
    d_v = v.shape[-1]
    c, chunks, dtype = chunk, seq // chunk, v.dtype
    # [n, head, position, ...]: a chunk's matrices with the positions and
    # the channels last
    to_chunks = lambda t: jnp.moveaxis(
        t.reshape(chunks, c, heads, *t.shape[2:]), 2, 1)
    q, k, v = to_chunks(q), to_chunks(k), to_chunks(v)  # [n, h, c, d]
    g = to_chunks(g.astype(state_dtype))                # [n, h, c, d_k]
    beta = to_chunks(beta.astype(state_dtype))[..., None]   # [n, h, c, 1]

    # G_i, the inclusive sum of g inside a chunk, as a product with a
    # triangle of ones: XLA's cumulative sum along the positions is a
    # reduce-window, 8 ms a call at the cell's shape where this is under 1
    cum = jnp.einsum("ij,nhjd->nhid", jnp.tril(jnp.ones((c, c), g.dtype)), g,
                     precision=precision)
    last = cum[..., -1:, :]                             # [n, h, 1, d_k]
    qk, kk = _decayed_products(q, k, cum.astype(_F32))
    # T = (I + N)^-1, N_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc), j < i
    inverse = unit_lower_inverse((beta * kk).astype(state_dtype),
                                 precision).astype(dtype)
    grown = jnp.exp(cum)                                # exp(G_i)
    k32 = k.astype(_F32)
    k_in = (k32 * (beta * grown)).astype(dtype)
    w = jnp.einsum("nhij,nhjd->nhid", inverse,
                   (v.astype(_F32) * beta).astype(dtype),
                   preferred_element_type=_F32)
    u = jnp.einsum("nhij,nhjd->nhid", inverse, k_in,
                   preferred_element_type=_F32).astype(dtype)
    k_out = (k32 * jnp.exp(last - cum)).astype(dtype)

    def carry(state, chunk_in):
        w_c, u_c, k_c, keep = chunk_in      # a chunk's, [h, ...]
        new = (w_c - jnp.einsum("hid,hde->hie", u_c, state.astype(dtype),
                                preferred_element_type=_F32)).astype(dtype)
        added = jnp.einsum("hid,hie->hde", k_c, new,
                           preferred_element_type=_F32)
        return (state * keep + added).astype(state_dtype), (new, state)

    _, (new, entering) = jax.lax.scan(
        carry, jnp.zeros((heads, d_k, d_v), state_dtype),
        (w, u, k_out, jnp.swapaxes(jnp.exp(last), -1, -2)))  # a row of S
    o = jnp.einsum("nhij,nhjd->nhid", qk.astype(dtype), new,     # a channel
                   preferred_element_type=_F32)
    q_in = (q.astype(_F32) * grown).astype(dtype)
    o = o + jnp.einsum("nhid,nhde->nhie", q_in, entering.astype(dtype),
                       preferred_element_type=_F32)
    return jnp.moveaxis(o, 1, 2).reshape(seq, heads, d_v).astype(dtype)
