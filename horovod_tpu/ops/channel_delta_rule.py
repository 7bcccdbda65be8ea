"""The chunked delta rule of ``models/kda.py`` (Kimi Delta Attention,
arXiv:2510.26692): ``ops/gated_delta_rule.py``'s rule with **a decay a key
channel** in place of one a head: its plain ``jax.numpy`` body, three
Pallas TPU kernels under one custom VJP, and the rule that chooses between
them.

``channel_delta_rule`` is what the mixer calls; ``serves`` sends it to the
kernels (``channel_delta_rule_kernels``) or to
``channel_delta_rule_plain``, which holds the equations, is the kernels'
reference and has ``jax.grad`` of itself for a backward pass.

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
  ``CHUNK`` and heads in whole 128-lane tiles; everything else runs the
  plain ``jax.numpy`` body, which has ``jax.grad`` of itself.

Here the second point takes more than a mask: the obvious fold ``(k_i
exp(G_i)) . (k_j exp(-G_j))`` takes the exponent of a positive cumulative
sum (``A`` up to 16). So a pair ``(i, j)`` of different positions factors
through a **reference row between them**: ``(k_i exp(G_i - G_r)) . (k_j
exp(G_r - G_j))``, both exponents non-positive, by halves: at each level
the rows in the second half of a block of ``2 h`` positions and the
columns in its first half share one ``r``, one product a level. The plain
body takes the second half's first row (``j < r <= i``) and starts the
levels at sub-blocks of ``SUB`` positions, inside which a pair takes the
difference ``G_i - G_j`` itself, channel by channel (``[SUB, SUB, d_k]``
float32 a sub-block). The kernels take the first half's last row (``j <=
r < i``) and start at single positions (``log2 c`` levels a chunk, every
pair through a product and only ``q_i . k_i`` beside them), and they never
subtract one cumulative sum from another: ``G_i - G_r`` is the sum of
``g`` from the second half's first row to ``i`` and ``G_r - G_j`` the sum
after ``j`` to the first half's end, which the doubling that makes ``G``
passes through on its way (``_running_sums``), so an exponent is as exact
as its own size allows and not as ``G``'s. And the third holds for both
operand dtypes a mixer is built with, bf16 and float32 (the family's
``float32_parts`` comparison runs the kernels the timed step runs):
inside the kernels float32 operands take the caller's precision on every
product.

The kernels are the siblings of ``ops/gated_delta_rule.py``'s, a chunk of
one head in VMEM, operands read and written as ``[b, s, H d]`` at a head's
columns:

- ``hvt_kda_inverse`` forms the sums of ``g`` and the decayed products
  ``N`` and inverts ``I + N``
  (``gated_delta_rule._unit_lower_inverse``, shared: the diagonal blocks
  of 16 by substitution on the VPU, the three levels above them by
  blocks, two float32 products each). It depends on no state, so a caller
  that recomputes its forward pass can keep its result (``KEPT_INVERSE``)
  and not run it twice.
- ``hvt_kda_fwd`` walks the chunks with the state of every head in a VMEM
  scratch, **transposed** (``[d_v, d_k]``: the decay of a key channel then
  scales lanes), and writes ``o`` and the state each chunk was entered
  with.
- ``hvt_kda_bwd`` walks them from the last to the first with ``dS`` in
  VMEM, makes a chunk's forward quantities again from the state that
  entered it and the kept inverse, and returns ``dq``, ``dk``, ``dv``,
  ``dbeta`` and ``dg`` **by channel**. The decayed products' pullback
  factors through the same folded operands as their forward, and what
  reaches ``G`` through them is ``q dq + k (dk_row - dk_col)`` (the
  reference rows cancel: no product depends on them).

``G`` is formed inside each kernel from ``g`` and ``dg`` from ``dG`` by
the same doubling, float32 additions in VMEM, so neither exists outside
them.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import gated_delta_rule as scalar_rule
from horovod_tpu.ops._pallas import NN, NT, TN
from horovod_tpu.ops.gated_delta_rule import unit_lower_inverse

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# The chunk the rule takes where the caller names none (the longest the
# sequence allows up to this) and the one chunk the kernels serve: a
# chunk's [c, c] matrices are then whole 128 x 128 tiles. SUB is the plain
# body's sub-block, inside which a pair of positions takes its decay
# channel by channel. A chunk's cost in the plain body is HBM traffic, not
# products: on a v5e at 2 x 8192, 32 heads of 128 x 128, bf16, a forward /
# a forward and backward call of it took 46.9 / 118.7 ms at 32 x 8, 50.1 /
# 130.3 at 64 x 8, 55.7 / 138.2 at 128 x 8, 47.1 / 128.4 at 32 x 4 and 49.1
# / 127.9 at 64 x 4 (benchmarks/kda_rule.py; PERF.md section 6, PR 55). The
# kernels' chunk: PERF.md section 6, PR 56.
CHUNK, SUB = 128, 8
# Heads of one sequence that pass at a time in the plain body (all of
# fewer).
HEADS_A_PASS = 8


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the rule uses for ``seq_len`` positions."""
    return max(1, min(chunk or CHUNK, seq_len))


def serves(chunk: int, key_dim: int, value_dim: int, dtype) -> bool:
    """Whether the rule goes to the kernels, from what can be observed
    (static trace-time facts, so the choice compiles away): a TPU backend,
    the chunk they were measured at, heads that fill whole 128-lane tiles,
    and operands in bf16 or float32. Everything else stays on
    ``channel_delta_rule_plain``, so the choice never raises for a shape
    that serves."""
    return (_pallas.on_tpu() and chunk == CHUNK
            and key_dim % 128 == 0 and value_dim % 128 == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(_F32)))


def channel_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                       state_dtype=_F32, precision=_HIGHEST):
    """The delta rule with a decay a channel, chunked:
    ``channel_delta_rule_plain``'s arguments and result, by the kernels
    where ``serves`` says so and by ``channel_delta_rule_plain`` itself
    everywhere else."""
    c = chunk_for(q.shape[1], chunk)
    rule = (channel_delta_rule_kernels
            if serves(c, q.shape[-1], v.shape[-1], v.dtype)
            else channel_delta_rule_plain)
    return rule(q, k, v, g, beta, chunk=c, state_dtype=state_dtype,
                precision=precision)


# ------------------------------------------------------------- plain body

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


# ------------------------------------------------- the kernels' arithmetic

class _Plan(NamedTuple):
    """All a kernel call is built from besides its operands' shapes. Made
    outside the jitted calls, so that what the process holds besides the
    operands (the backend, the caller's constants) is part of their
    cache's key and never read under a cached trace."""
    chunk: int
    heads: int
    key_dim: int
    value_dim: int
    head_block: int     # heads a grid step takes
    state_dtype: Any    # what the sums, the system, its inverse and the
                        # carried state are rounded to (float32: not at all)
    precision: Any      # of the float32 products
    interpret: bool


def _count_trace(kernel, plan):
    """Which kernels a job got, by the chunk and head widths, and which
    inverse: ``solved``, the width of the blocks taken by substitution."""
    _pallas.count_trace(
        "hvt_kda_kernel_traces_total",
        "delta rule kernels with a decay a key channel traced into "
        "compiled programs (counted per trace, not per execution)",
        kernel=kernel, chunk=plan.chunk, key_dim=plan.key_dim,
        value_dim=plan.value_dim, solved=scalar_rule._solved(plan.chunk))


def _carried(plan):
    """What rounds the state a chunk hands on: ``_rounder`` of the plan's
    state dtype, as everything else float32 (float32, the dtype the
    program runs, is no operation at all). A name of its own so that a
    builder's experiment can round the carried state alone
    (``benchmarks/kimilinear_wrong_programs.py``)."""
    return scalar_rule._rounder(plan.state_dtype)


def _products(plan):
    """``(product, product32)``: a product of two operands in the
    operands' dtype, accumulated in float32 (float32 operands take the
    plan's precision), and one of two float32 matrices whatever the
    operands' dtype."""
    def product32(a, b, dims):
        return jax.lax.dot_general(a, b, dims, precision=plan.precision,
                                   preferred_element_type=_F32)

    def product(a, b, dims):
        if a.dtype == _F32:
            return product32(a, b, dims)
        return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)

    return product, product32


class _Decays(NamedTuple):
    """What a chunk's decays come to, all float32 and every factor in
    ``[0, 1]``: ``G [c, d_k]`` the inclusive sum of ``g``."""
    grown: Any      # exp(G) [c, d_k]
    left: Any       # exp(G_last - G) [c, d_k]
    kept: Any       # exp(G_last) [1, d_k]
    levels: Any     # a level h = 1, 2, 4, ...: a row in the second half of
                    # its block of 2 h has exp(G_i - G_r) and one in the
                    # first exp(G_r - G_j), r the first half's last row
                    # (j <= r < i), [c, d_k]


def _of_block(x, x_ref, at, h, place):
    """Row ``place`` of each block of ``2 h`` rows of ``x [c, d]``, at
    every row of that block (``c`` a multiple of ``2 h``); ``x_ref`` is
    VMEM that holds ``x``. Blocks of whole sublane tiles read their row
    and broadcast it; smaller ones take it from ``place - m`` rows on, ``m``
    a row's own place in its block."""
    c, d = x.shape
    if h % 4 == 0:
        return _stacked([jnp.broadcast_to(x_ref[r:r + 1, :], (2 * h, d))
                         for r in range(place, c, 2 * h)])
    out = x
    for m in range(2 * h):
        if m != place:
            out = jnp.where((at & (2 * h - 1)) == m,
                            pltpu.roll(x, (m - place) % c, 0), out)
    return out


def _running_sums(x, x_ref, low):
    """The sums of ``x [c, d]`` float32 over the positions of a chunk,
    ``c`` a power of two, by doubling: yields ``(before, after)`` at each
    level ``h = 1, 2, 4, ..., c``, for a row ``i`` of a block of ``h``
    rows the sum of ``x`` from the block's first row to ``i`` and from
    ``i + 1`` to its last. Nothing is subtracted, so a sum of non-positive
    numbers is one, to the last bit, and as exact as its own size allows
    (a difference of two cumulative sums is as exact as theirs). ``x_ref``
    is VMEM for the level's sums, ``low`` rounds them."""
    c, d = x.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (c, d), 0)
    before, after = low(x), jnp.zeros_like(x)
    shift = 0
    while (h := 1 << shift) < c:
        yield before, after
        x_ref[...] = before
        second = ((at >> shift) & 1) == 1
        # the first half's sum onto the second half's rows, the second's
        # onto the first's
        after = low(after + jnp.where(
            second, 0.0, _of_block(before, x_ref, at, h, 2 * h - 1)))
        before = low(before + jnp.where(
            second, _of_block(before, x_ref, at, h, h - 1), 0.0))
        shift += 1
    yield before, after


def _decays(g, sum_ref, plan):
    """``_Decays`` of one chunk and head from ``g [c, d_k]`` float32 (``g
    <= 0``); ``sum_ref [c, d_k]`` float32 is VMEM for the running sums.
    Every exponent is a sum of ``g`` over the positions between two rows,
    taken as that sum and never as a difference."""
    c = g.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    *levels, (cum, rest) = _running_sums(
        g, sum_ref, scalar_rule._rounder(plan.state_dtype))
    sum_ref[...] = cum
    return _Decays(
        jnp.exp(cum), jnp.exp(rest), jnp.exp(sum_ref[c - 1:c, :]),
        [jnp.exp(jnp.where(((at >> shift) & 1) == 1, before, after))
         for shift, (before, after) in enumerate(levels)])


def _quarter(row, col, shift):
    """Rows in the second half of a block of ``2 h``, columns in its
    first, ``h = 2^shift``: every pair ``j < i`` is in one level's."""
    return ((((row ^ col) >> shift) == 1) & (((row >> shift) & 1) == 1))


def _stacked(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _level_products(rows, k32, levels, row, col, dtype, product):
    """``sum_c x_ic k_jc exp(G_ic - G_jc)`` for ``j < i`` (0 on and above
    the diagonal), ``x`` each of ``rows`` (float32 ``[c, d_k]``), stacked
    ``[len(rows) c, c]`` float32: a level's product of the rows folded
    with that level's factors on the keys folded with them, kept in the
    level's quarters."""
    c = k32.shape[0]
    n = len(rows)
    total = jnp.zeros((n * c, c), _F32)
    for shift, factor in enumerate(levels):
        level = product(
            _stacked([(x * factor).astype(dtype) for x in rows]),
            (k32 * factor).astype(dtype), NT)
        total = total + jnp.where(
            _stacked([_quarter(row, col, shift)] * n), level, 0.0)
    return total


def _system_inverse(k, g, beta, sum_ref, row, col, plan):
    """``T = (I + N)^-1`` of one chunk and head, float32: ``N_ij = beta_i
    sum_c k_ic k_jc exp(G_ic - G_jc)`` for ``j < i``."""
    low = scalar_rule._rounder(plan.state_dtype)
    product, _ = _products(plan)
    k32 = k.astype(_F32)
    decays = _decays(g, sum_ref, plan)
    kk = _level_products([k32], k32, decays.levels, row, col, k.dtype,
                           product)
    return scalar_rule._unit_lower_inverse(low(beta * kk), row, col, plan)


class _Chunk(NamedTuple):
    """A chunk's forward quantities, for one head: what the backward pass
    reads besides the operands."""
    o: Any          # [c, d_v] float32
    state: Any      # [d_v, d_k] float32, what the chunk hands on
    decays: Any     # _Decays
    kk: Any         # the keys' decayed products, j < i (None: not asked)
    v_in: Any       # beta v
    k_in: Any       # beta k exp(G)
    u: Any          # T k_in, in the operands' dtype
    new: Any        # the chunk's corrections, in the operands' dtype
    inside: Any     # the queries' decayed products, j <= i, in the
                    # operands' dtype
    q_in: Any       # q exp(G)
    k_out: Any      # k exp(G_last - G)


def _chunk(state, q, k, v, g, beta, inverse, sum_ref, row, col, plan, *,
           with_keys=False):
    """One chunk of one head entered with ``state [d_v, d_k]`` float32
    (transposed): ``q``, ``k`` ``[c, d_k]``, ``v [c, d_v]``, ``g [c, d_k]``
    and ``beta [c, 1]`` float32 and the inverse of the chunk's system ``[c,
    c]`` in the operands' dtype. ``with_keys``: the keys' decayed products
    too, which the backward pass reads."""
    dtype = v.dtype
    c = q.shape[0]
    product, _ = _products(plan)
    decays = _decays(g, sum_ref, plan)
    q32, k32 = q.astype(_F32), k.astype(_F32)
    both = _level_products([q32, k32] if with_keys else [q32], k32,
                             decays.levels, row, col, dtype, product)
    # a position reads its own correction undecayed: q_i . k_i
    qk = both[:c] + jnp.where(row == col, jnp.sum(
        q32 * k32, axis=1, keepdims=True), 0.0)
    v_in = (v.astype(_F32) * beta).astype(dtype)
    k_in = (k32 * (beta * decays.grown)).astype(dtype)
    w = product(inverse, v_in, NN)
    u = product(inverse, k_in, NN).astype(dtype)
    entered = state.astype(dtype)
    new = (w - product(u, entered, NT)).astype(dtype)
    inside = qk.astype(dtype)
    q_in = (q32 * decays.grown).astype(dtype)
    o = product(inside, new, NN) + product(q_in, entered, NT)
    k_out = (k32 * decays.left).astype(dtype)
    handed = _carried(plan)(state * decays.kept + product(new, k_out, TN))
    return _Chunk(o, handed, decays, both[c:] if with_keys else None, v_in,
                  k_in, u, new, inside, q_in, k_out)


def _chunk_backward(at, state, q, k, v, g, beta, inverse, do, d_handed,
                    sum_ref, row, col, plan):
    """The transpose of ``_chunk`` at ``at`` (its forward quantities, the
    keys' products among them): from ``do [c, d_v]`` and ``d_handed [d_v,
    d_k]`` float32 to ``(dq, dk, dv, dg, dbeta, d_state)``, all float32,
    ``dg [c, d_k]`` by channel. Products in the operands' dtype with
    float32 accumulation, as the forward's; the inverse's transpose is
    ``-T^T g T^T``, two float32 products at ``plan.precision``, and ``dg``
    the sum of ``dG`` from a position to the chunk's end
    (``_running_sums``, for which ``sum_ref`` is VMEM)."""
    dtype = v.dtype
    c = q.shape[0]
    product, product32 = _products(plan)
    rows = lambda t: jnp.sum(t, axis=1, keepdims=True)      # [c, 1]
    q32, k32, v32 = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    grown, left, kept, levels = at.decays
    entered = state.astype(dtype)
    do = do.astype(dtype)
    handed = d_handed.astype(dtype)

    # o = inside new + q_in S;  S' = kept S + k_out^T new  (S transposed)
    d_inside = product(do, at.new, NT)                      # [c, c]
    d_new = product(at.inside, do, TN) + product(at.k_out, handed, NT)
    d_q_in = product(do, entered, NN)                       # [c, d_k]
    d_k_out = product(at.new, handed, NN)                   # [c, d_k]
    # new = T v_in - u S
    d_new = d_new.astype(dtype)
    d_u = -product(d_new, entered, NN)                      # [c, d_k]
    d_state = (d_handed * kept + product(do, at.q_in, TN)
               - product(d_new, at.u, TN))
    d_u = d_u.astype(dtype)
    d_inverse = product(d_new, at.v_in, NT) + product(d_u, at.k_in, NT)
    d_v_in = product(inverse, d_new, TN)                    # [c, d_v]
    d_k_in = product(inverse, d_u, TN)                      # [c, d_k]
    # T = (I + N)^-1, N = beta kk (strictly lower)
    inverse32 = inverse.astype(_F32)
    d_system = jnp.where(row > col, -product32(
        inverse32, product32(d_inverse, inverse32, NT), TN), 0.0)
    # the decayed products, a level at a time through the operands they
    # were folded into: P = (x fall)(k rise)^T gives d(x fall) = dP (k
    # rise) and d(k rise) = dP^T (x fall)
    by = (d_inside, d_system * beta)
    d_q = d_k_row = d_k_col = jnp.zeros_like(k32)
    for shift, factor in enumerate(levels):
        quarter = _quarter(row, col, shift)
        d_level = _stacked([jnp.where(quarter, d, 0.0).astype(dtype)
                            for d in by])                   # [2 c, c]
        folded = (k32 * factor).astype(dtype)
        d_rows = product(d_level, folded, NN)               # [2 c, d_k]
        d_cols = product(d_level, _stacked(
            [(q32 * factor).astype(dtype), folded]), TN)    # [c, d_k]
        d_q = d_q + d_rows[:c] * factor
        d_k_row = d_k_row + d_rows[c:] * factor
        d_k_col = d_k_col + d_cols * factor
    # q_i . k_i, which no decay reaches
    diagonal = rows(jnp.where(row == col, d_inside, 0.0))
    dq = d_q + diagonal * k32 + d_q_in * grown
    dk = (d_k_row + d_k_col + diagonal * q32 + d_k_in * (beta * grown)
          + d_k_out * left)
    dv = d_v_in * beta
    # k_in = k beta exp(G), v_in = v beta
    d_scaled = d_k_in * k32 * grown                 # of beta, a channel
    dbeta = rows(d_system * at.kk) + rows(d_scaled) + rows(d_v_in * v32)
    # what reaches G_i: through the products q dq + k (dk_row - dk_col),
    # whatever the reference rows were (q_i . k_i is not among them: it
    # would cancel against itself, and it is the size of everything a
    # strong decay makes small); through q_in and k_in by exp(G), through
    # k_out by exp(G_last - G), and the last row by exp(G_last); and g_t
    # is in G_i for every i >= t
    at_row = jax.lax.broadcasted_iota(jnp.int32, q32.shape, 0)
    d_left = d_k_out * k32 * left
    d_last = (jnp.sum(d_left, axis=0, keepdims=True)
              + kept * jnp.sum(d_handed * state, axis=0, keepdims=True))
    d_cum = (q32 * d_q + k32 * (d_k_row - d_k_col) + d_scaled * beta
             + d_q_in * q32 * grown - d_left
             + jnp.where(at_row == c - 1, d_last, 0.0))
    *_, (_, later) = _running_sums(d_cum, sum_ref, lambda x: x)
    dg = d_cum + later
    return dq, dk, dv, dg, dbeta, d_state


# ---------------------------------------------------------------- kernels
#
# Grid (batch, chunk, block of heads), the heads innermost, so that the [c,
# H] tiles of beta and its gradient stay where they are while the heads
# pass and each head writes its own column. A chunk's system does not
# depend on the state that enters the chunk, so its inverse has a kernel
# of its own with nothing to carry (``hvt_kda_inverse``); the forward and
# backward kernels walk the chunk axis in order (the backward's index maps
# turn it round) with the states in scratch.

_HEADS_A_STEP = 2   # a step's heads are unrolled, so the kernels' bodies and
                    # the time to trace and lower a step go by them. On a
                    # v5e at 2 x 8192, 32 heads of 128 x 128, 2 and 4 a
                    # step gave a forward and backward call of 26.3 and
                    # 24.7 ms, and with 1, 2 and 4 kimilinear-s8192's step
                    # was traced and lowered in 11.3, 12.5 and 23.2 s (11.1
                    # on the plain body; `setup_s` is an end-to-end
                    # metric): PERF.md section 6, PR 56


def _head_block(heads):
    return max(n for n in range(1, _HEADS_A_STEP + 1) if heads % n == 0)


def _step_heads(plan, hb):
    """``(j, h, at_k, at_v)`` of each head a grid step takes: its place in
    the step's blocks, its index among all heads and the two column
    slices."""
    d_k, d_v = plan.key_dim, plan.value_dim
    return [(j, hb * plan.head_block + j, slice(j * d_k, (j + 1) * d_k),
             slice(j * d_v, (j + 1) * d_v)) for j in range(plan.head_block)]


def _betas(beta_ref, heads):
    """``beta [c, 1]`` of each of a step's heads, from the ``[c, H]``
    tile."""
    betas = beta_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    return [jnp.sum(jnp.where(lane == h, betas, 0.0), axis=1, keepdims=True)
            for _, h, *_ in heads], lane


def _inverse_kernel(k_ref, g_ref, beta_ref, inverse_ref, sum_ref, *, plan):
    heads = _step_heads(plan, pl.program_id(2))
    row, col = scalar_rule._positions(plan.chunk)
    betas, _ = _betas(beta_ref, heads)
    done = [_system_inverse(k_ref[0, :, at_k], g_ref[0, :, at_k], beta,
                            sum_ref.at[j], row, col, plan)
            for beta, (j, _, at_k, _) in zip(betas, heads)]
    for inverse, (j, *_) in zip(done, heads):
        inverse_ref[0, 0, j] = inverse.astype(inverse_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref, o_ref,
                entering_ref, sum_ref, state_ref, *, plan):
    ni, hb = pl.program_id(1), pl.program_id(2)
    heads = _step_heads(plan, hb)
    row, col = scalar_rule._positions(plan.chunk)
    betas, _ = _betas(beta_ref, heads)

    @pl.when(ni == 0)
    def _first_chunk():
        for _, h, *_ in heads:
            state_ref[h] = jnp.zeros(state_ref.shape[1:], _F32)

    states = [state_ref[h] for _, h, *_ in heads]
    done = [_chunk(state, q_ref[0, :, at_k], k_ref[0, :, at_k],
                   v_ref[0, :, at_v], g_ref[0, :, at_k], beta,
                   inverse_ref[0, 0, j], sum_ref.at[j], row, col, plan)
            for state, beta, (j, _, at_k, at_v)
            in zip(states, betas, heads)]
    for state, at, (j, h, _, at_v) in zip(states, done, heads):
        entering_ref[0, 0, j] = state
        o_ref[0, :, at_v] = at.o.astype(o_ref.dtype)
        state_ref[h] = at.state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                entering_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, sum_ref, d_state_ref, *, plan):
    ni, hb = pl.program_id(1), pl.program_id(2)
    heads = _step_heads(plan, hb)
    row, col = scalar_rule._positions(plan.chunk)
    betas, lane = _betas(beta_ref, heads)

    @pl.when(hb == 0)
    def _first_heads():
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    @pl.when(ni == 0)               # the last chunk: nothing comes after
    def _last_chunk():
        for _, h, *_ in heads:
            d_state_ref[h] = jnp.zeros(d_state_ref.shape[1:], _F32)

    def one(beta, j, h, at_k, at_v):
        operands = (entering_ref[0, 0, j], q_ref[0, :, at_k],
                    k_ref[0, :, at_k], v_ref[0, :, at_v], g_ref[0, :, at_k],
                    beta, inverse_ref[0, 0, j])
        return _chunk_backward(
            _chunk(*operands, sum_ref.at[j], row, col, plan, with_keys=True),
            *operands, do_ref[0, :, at_v], d_state_ref[h], sum_ref.at[j], row,
            col, plan)

    done = [one(beta, *head) for beta, head in zip(betas, heads)]
    dbetas = dbeta_ref[0]
    for (dq, dk, dv, dg, dbeta, d_state), (_, h, at_k, at_v) in zip(
            done, heads):
        dq_ref[0, :, at_k] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, at_k] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, at_v] = dv.astype(dv_ref.dtype)
        dg_ref[0, :, at_k] = dg
        dbetas = jnp.where(lane == h, dbeta, dbetas)
        d_state_ref[h] = d_state
    dbeta_ref[0] = dbetas


def _specs(plan, chunk_of):
    """Block specs of a call's operands by kind; ``chunk_of(ni)`` is the
    chunk the grid's step ``ni`` works on."""
    c = plan.chunk
    per_head = lambda *tile: pl.BlockSpec(
        (1, 1, plan.head_block, *tile),
        lambda bi, ni, hb: (bi, chunk_of(ni), hb, 0, 0))
    return {
        "key": pl.BlockSpec((1, c, plan.head_block * plan.key_dim),
                            lambda bi, ni, hb: (bi, chunk_of(ni), hb)),
        "value": pl.BlockSpec((1, c, plan.head_block * plan.value_dim),
                              lambda bi, ni, hb: (bi, chunk_of(ni), hb)),
        "column": pl.BlockSpec((1, c, plan.heads),
                               lambda bi, ni, hb: (bi, chunk_of(ni), 0)),
        "state": per_head(plan.value_dim, plan.key_dim),
        "inverse": per_head(c, c),
    }


def _call(kernel, name, plan, operands, in_specs, out_specs, out_shape, *,
          carries):
    """One of the three Pallas calls; ``operands`` are ``[b, s, ...]``.
    Every kernel has VMEM for the ``G`` of a step's heads; ``carries``:
    and for a state a head, kept from one chunk to the next."""
    batch, seq = operands[0].shape[:2]
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid=(batch, seq // plan.chunk, plan.heads // plan.head_block),
        in_specs=in_specs, out_specs=out_specs,
        out_shape=[_pallas.out(shape, dtype, *operands)
                   for shape, dtype in out_shape],
        scratch_shapes=[
            pltpu.VMEM((plan.head_block, plan.chunk, plan.key_dim), _F32),
            *([pltpu.VMEM((plan.heads, plan.value_dim, plan.key_dim), _F32)]
              if carries else [])],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", *(("arbitrary",) * 2 if carries
                          else ("parallel",) * 2))),
        interpret=plan.interpret, name=name)(*operands)


def _per_head(plan, operand, *tile):
    batch, seq = operand.shape[:2]
    return (batch, seq // plan.chunk, plan.heads, *tile)


# Each call is a ``jax.jit`` of its own, as the flash kernels' are: a
# model's layers share one trace and one lowered function a kernel.
@functools.partial(jax.jit, static_argnames=("plan", "dtype"))
def _inverse_call(k, g, beta, *, plan, dtype):
    """``k [b, s, H d_k]``, ``g [b, s, H d_k]`` and ``beta [b, s, H]``
    float32, ``s`` a multiple of the chunk -> the inverse of each chunk's
    and head's system, ``[b, n, H, c, c]``, made in float32 and written in
    ``dtype``, the one the products take it in."""
    _count_trace("inverse", plan)
    spec = _specs(plan, lambda ni: ni)
    return _call(
        _inverse_kernel, "hvt_kda_inverse", plan, (k, g, beta),
        [spec["key"], spec["key"], spec["column"]], [spec["inverse"]],
        [(_per_head(plan, k, plan.chunk, plan.chunk), dtype)],
        carries=False)[0]


@functools.partial(jax.jit, static_argnames="plan")
def _fwd_call(q, k, v, g, beta, inverse, *, plan):
    """``q``, ``k`` ``[b, s, H d_k]``, ``v [b, s, H d_v]``, ``g``, ``beta``
    and ``inverse`` as ``_inverse_call`` has them -> ``o`` like ``v`` and
    the state each chunk and head was entered with, transposed, ``[b, n,
    H, d_v, d_k]`` float32, which the backward pass reads."""
    _count_trace("fwd", plan)
    spec = _specs(plan, lambda ni: ni)
    return _call(
        _fwd_kernel, "hvt_kda_fwd", plan, (q, k, v, g, beta, inverse),
        [spec["key"], spec["key"], spec["value"], spec["key"],
         spec["column"], spec["inverse"]],
        [spec["value"], spec["state"]],
        [(v.shape, v.dtype),
         (_per_head(plan, v, plan.value_dim, plan.key_dim), _F32)],
        carries=True)


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(q, k, v, g, beta, inverse, entering, do, *, plan):
    """``(dq, dk, dv, dg, dbeta)`` for ``_fwd_call``'s operands with the
    inverse a function of them, ``dg [b, s, H d_k]`` and ``dbeta [b, s,
    H]`` float32."""
    _count_trace("bwd", plan)
    chunks = v.shape[1] // plan.chunk
    spec = _specs(plan, lambda ni: chunks - 1 - ni)
    return _call(
        _bwd_kernel, "hvt_kda_bwd", plan,
        (q, k, v, g, beta, inverse, entering, do),
        [spec["key"], spec["key"], spec["value"], spec["key"],
         spec["column"], spec["inverse"], spec["state"], spec["value"]],
        [spec["key"], spec["key"], spec["value"], spec["key"],
         spec["column"]],
        [(q.shape, q.dtype), (k.shape, k.dtype), (v.shape, v.dtype),
         (g.shape, _F32), (beta.shape, _F32)],
        carries=True)


# The name (``jax.ad_checkpoint.checkpoint_name``) of the chunks'
# inverses, for a caller that recomputes its forward pass and would keep
# them: they do not depend on the carried state, at ``c`` numbers in the
# operands' dtype a position and head (134 MB a layer of 2 x 8192 x 32 in
# bf16), so ``models.GPT`` keeps them under ``remat`` and the recomputed
# layer runs the forward walk alone (for the states the backward reads).
KEPT_INVERSE = "kda_rule_inverse"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, plan):
    return _rule_fwd(q, k, v, g, beta, plan)[0]


def _rule_fwd(q, k, v, g, beta, plan):
    inverse = checkpoint_name(
        _inverse_call(k, g, beta, plan=plan, dtype=v.dtype), KEPT_INVERSE)
    o, entering = _fwd_call(q, k, v, g, beta, inverse, plan=plan)
    return o, (q, k, v, g, beta, inverse, entering)


def _rule_bwd(plan, res, do):
    return _bwd_call(*res, do, plan=plan)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _prepare(q, k, v, g, beta, chunk, state_dtype, precision):
    batch, seq, heads, d_k = q.shape
    chunk = 1 << (chunk - 1).bit_length()   # the levels halve it
    pad = -seq % chunk
    if pad:
        grow = lambda t: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    flat = lambda t: t.reshape(batch, seq + pad, -1)
    stored = scalar_rule._stored_as(state_dtype)
    plan = _Plan(chunk, heads, d_k, v.shape[-1], _head_block(heads),
                 jnp.dtype(state_dtype), precision, _pallas.interpret())
    return plan, (flat(q), flat(k), flat(v), flat(stored(g)),
                  stored(beta)), pad


def channel_delta_rule_kernels(q, k, v, g, beta, *, chunk, state_dtype=_F32,
                               precision=_HIGHEST):
    """``channel_delta_rule_plain`` through the kernels: its arguments (the
    chunk named; one that is no power of two is taken as the next) and
    its result. Differentiable in all five. A sequence the chunk does not
    divide is padded with positions whose ``g`` and ``beta`` are 0."""
    seq = q.shape[1]
    plan, operands, pad = _prepare(q, k, v, g, beta, chunk, state_dtype,
                                   precision)
    return _rule(*operands, plan).reshape(
        v.shape[0], seq + pad, *v.shape[2:])[:, :seq]
