"""The chunked gated delta rule of ``models/gdn.py``: its plain
``jax.numpy`` body, three Pallas TPU kernels under one custom VJP, and the
rule that chooses between them.

``gated_delta_rule`` is what the mixer calls; ``serves`` sends it to the
kernels (``gated_delta_rule_kernels``) or to ``gated_delta_rule_plain``,
which holds the equations, is the kernels' reference and has ``jax.grad``
of itself for a backward pass.

In plain ``jax.numpy`` a chunk's ``[c, c]`` float32 matrices (the decay
mask, ``k k^T``, ``q k^T``, the triangular system, its inverse and that
inverse's intermediates) each pass through HBM, the operands are copied
between ``[b, s, h, d]`` and a chunked layout, and the carry over the
chunks is an XLA loop of small batched products. In the kernels a chunk of
one value head is a few hundred KB of VMEM and none of that exists outside
it:

- ``hvt_gdn_inverse`` forms a chunk's decays and system in registers and
  inverts it (``_unit_lower_inverse``: the diagonal blocks of 16 by
  forward substitution on the VPU, exact float32 and no product, then
  three levels by blocks, two float32 products each). It depends on no
  state, so it carries nothing and a caller that recomputes its forward
  pass can keep its result (``KEPT_INVERSE``) and not run it twice.
- ``hvt_gdn_fwd`` walks the chunks itself with the state ``S [d_k, d_v]``
  of every value head in a VMEM scratch and writes ``o`` and, for the
  backward pass, the state each chunk was *entered with*.
- ``hvt_gdn_bwd`` walks the chunks from the last to the first with ``dS``
  in VMEM, makes a chunk's forward quantities again from the state that
  entered it and the kept inverse, and returns ``dq``, ``dk`` (summed over
  the value heads of a key head), ``dv``, ``dG`` and ``dbeta``.

All read ``q``, ``k``, ``v`` as ``[b, s, H d]`` (a block ``(1, c, d)`` at a
head's columns: the convolution's own output layout) and write the same
way.

**The contract both delta rules keep** (this module and
``ops/channel_delta_rule.py``, the rule with a decay a key channel, say it
in the same words):

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

Here the decay is one number a head and position, so ``exp(G_i - G_j)`` is
a ``[c, c]`` mask that multiplies ``k k^T`` and ``q k^T`` *after* the
products; a decay a channel sits inside the sum over channels and is
folded into the operands through reference rows (the sibling module,
whose three kernels take the inverse, ``_unit_lower_inverse`` (blocks of
16 by substitution, the levels above them by products), and the rounders
of a state dtype steered from outside from here).
Both bodies here keep the contract, the kernels carry ``dS`` in float32
too, and the cumulative sum of ``g`` inside a chunk and its transpose for
``dg`` are XLA operations around the kernels (``[b, s, H_v]`` float32).

On the CPU the same kernel code runs through the Pallas interpreter, at
any chunk and head width; compiled, Mosaic wants a chunk and head widths
that are multiples of 128 (``serves`` sends it nothing else).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import _pallas
from horovod_tpu.ops._pallas import NN, NT, TN, dot

_F32 = jnp.float32
# The chunk the rule takes where the caller names none: the longest the
# sequence allows up to this, and the one chunk the kernels serve (a
# chunk's [c, c] matrices are then whole 128 x 128 tiles). The inverse
# costs by the chunk's square a position and the carry by the number of
# chunks. On a v5e at 2 x 8192, 32 value heads of 128 x 128 on 16 key
# heads, bf16, a forward and backward of the plain body took 50.4 ms at 64
# (the source's kernel's), 49.8 at 128 and 87.1 at 256 (PERF.md section 6,
# PR 33); at 128 the states kept for the backward pass are half as many as
# at 64. The kernels at 128: chip_smoke.py's gdn8192 and PERF.md section
# 6, PR 34.
CHUNK = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the rule uses for ``seq_len`` positions."""
    return max(1, min(chunk or CHUNK, seq_len))


def serves(chunk: int, key_dim: int, value_dim: int) -> bool:
    """Whether the rule goes to the kernels, from what can be observed
    (static trace-time facts, so the choice compiles away): a TPU backend,
    the chunk they were measured at, and heads that fill whole 128-lane
    tiles. Everything else stays on ``gated_delta_rule_plain``, so the
    choice never raises for a shape that serves."""
    return (_pallas.on_tpu() and chunk == CHUNK
            and key_dim % 128 == 0 and value_dim % 128 == 0)


def gated_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                     state_dtype=_F32, precision=_HIGHEST):
    """The gated delta rule, chunked: ``gated_delta_rule_plain``'s
    arguments and result, by the kernels where ``serves`` says so and by
    ``gated_delta_rule_plain`` itself everywhere else."""
    c = chunk_for(q.shape[1], chunk)
    rule = (gated_delta_rule_kernels
            if serves(c, q.shape[-1], v.shape[-1])
            else gated_delta_rule_plain)
    return rule(q, k, v, g, beta, chunk=c, state_dtype=state_dtype,
                precision=precision)


# ------------------------------------------------------------- plain body

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(lower, precision=_HIGHEST):
    """``(I + N)^-1`` for ``N`` the strictly lower triangle of ``lower
    [..., c, c]`` (what is on or above the diagonal is not read), by
    blocks: the inverse of the diagonal blocks of size ``m`` is that of
    the blocks of size ``2 m`` once ``T <- T - T O T`` has been taken with
    ``O`` the part of ``N`` in the lower left quarter of each ``2 m``
    block (``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``).
    From ``m = 1``, where the inverse is the identity, ``ceil(log2 c)``
    such steps, two ``[c, c]`` products each, every intermediate the true
    inverse of a block-diagonal part of the system (no power of ``N`` is
    ever formed). In ``lower``'s dtype, its products at ``precision``. The
    backward pass is the inverse's own, ``-T^T g T^T``, so that it keeps
    ``T`` and no step's intermediate."""
    c = lower.shape[-1]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    n = jnp.tril(lower, -1)
    inverse = jnp.broadcast_to(jnp.eye(c, dtype=lower.dtype), n.shape)
    m = 1
    while m < c:
        quarter = ((row // (2 * m) == col // (2 * m))
                   & ((row // m) % 2 == 1) & ((col // m) % 2 == 0))
        inverse = inverse - jnp.matmul(
            jnp.matmul(inverse, jnp.where(quarter, n, 0.0),
                       precision=precision), inverse, precision=precision)
        m *= 2
    return inverse


def _unit_lower_inverse_fwd(lower, precision):
    inverse = unit_lower_inverse(lower, precision)
    return inverse, inverse


def _unit_lower_inverse_bwd(precision, inverse, g):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (jnp.tril(-jnp.matmul(
        jnp.matmul(transposed, g, precision=precision), transposed,
        precision=precision), -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule_plain(q, k, v, g, beta, *, chunk: Optional[int] = None,
                           state_dtype=_F32, precision=_HIGHEST):
    """The gated delta rule, chunked, in plain ``jax.numpy``: the path of
    every backend and shape the kernels do not serve, and their reference.

    ``q``, ``k`` ``[batch, s, H_k, d_k]`` (normalised and scaled by the
    caller), ``v [batch, s, H_v, d_v]``, ``g`` and ``beta`` ``[batch, s,
    H_v]`` float32 (``g <= 0``); value head ``h`` reads key head ``h //
    (H_v / H_k)``. Returns ``o [batch, s, H_v, d_v]`` in ``v.dtype`` with
    ``S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - exp(g_t) S_{t-1}^T
    k_t))^T`` from ``S = 0`` and ``o_t = S_t^T q_t``.

    With ``G_i`` the cumulative sum of ``g`` inside a chunk, the
    corrections ``u_i = beta_i (v_i - S'_i^T k_i)`` of a chunk entered
    with state ``S`` solve ``(I + N) u = beta v - (beta k exp(G)) S``,
    ``N`` the strictly lower triangle of ``beta_i (k_i . k_j) exp(G_i -
    G_j)``: with ``T = (I + N)^-1`` (``unit_lower_inverse``), ``W = T
    (beta v)`` and ``U = T (beta k exp(G))`` are known before the carry
    and ``u = W - U S`` inside it. A position reads ``o_i = (q_i exp(G_i))
    S + sum_{j <= i} (q_i . k_j) exp(G_i - G_j) u_j`` and the chunk hands
    on ``exp(G_last) S + (k exp(G_last - G))^T u``. Every exponent is of a
    non-positive number. The carry is a ``lax.scan`` over the chunks (the
    corrections of a chunk depend on the state that enters it); all else
    is batched over them. A sequence the chunk does not divide is padded
    with positions whose ``g`` and ``beta`` are 0 (they decay nothing,
    write nothing and are cut off again). ``state_dtype`` is what the
    decays, their sums, the inverse and the carried state are computed in
    and ``precision`` that of the float32 products that invert the system:
    the caller's constants (``gdn.STATE_DTYPE``, ``gdn.INVERSE_PRECISION``).
    """
    batch, seq, key_heads, d_k = q.shape
    value_heads, d_v = v.shape[-2:]
    per_key = value_heads // key_heads
    c = chunk_for(seq, chunk)
    pad = -seq % c
    if pad:
        grow = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    chunks = (seq + pad) // c
    dtype = v.dtype
    # [b, n, key head, value head of it, position, ...]: a chunk's [c, c]
    # matrices with the positions last, so that they are the tile
    to_key = lambda t: jnp.transpose(
        t.reshape(batch, chunks, c, key_heads, d_k), (0, 1, 3, 2, 4))
    to_value = lambda t, *last: jnp.transpose(
        t.reshape(batch, chunks, c, key_heads, per_key, *last),
        (0, 1, 3, 4, 2) + tuple(range(5, 5 + len(last))))
    q, k = to_key(q), to_key(k)                     # [b, n, K, c, d_k]
    v = to_value(v, d_v)                            # [b, n, K, r, c, d_v]
    g = to_value(g.astype(state_dtype))             # [b, n, K, r, c]
    beta = to_value(beta.astype(state_dtype))

    cum = jnp.cumsum(g, axis=-1)                    # G_i, inclusive
    last = cum[..., -1]                             # [b, n, K, r]
    lag = cum[..., :, None] - cum[..., None, :]     # G_i - G_j
    at, before = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(at >= before, lag, -jnp.inf)).astype(_F32)
    kk = jnp.einsum("bnkid,bnkjd->bnkij", k, k, preferred_element_type=_F32)
    qk = jnp.einsum("bnkid,bnkjd->bnkij", q, k, preferred_element_type=_F32)
    # T = (I + N)^-1, N_ij = beta_i (k_i . k_j) exp(G_i - G_j) for j < i
    inverse = unit_lower_inverse(
        (beta[..., :, None] * kk[:, :, :, None] * decay).astype(state_dtype),
        precision).astype(dtype)
    by_key = lambda t: t[:, :, :, None]             # beside its value heads
    k_in = by_key(k).astype(_F32) * (beta * jnp.exp(cum))[..., None]
    w = jnp.einsum("bnkrij,bnkrjd->bnkrid", inverse,
                   (v.astype(_F32) * beta[..., None]).astype(dtype),
                   preferred_element_type=_F32)
    u = jnp.einsum("bnkrij,bnkrjd->bnkrid", inverse, k_in.astype(dtype),
                   preferred_element_type=_F32)
    k_out = (by_key(k).astype(_F32)
             * jnp.exp(last[..., None] - cum)[..., None]).astype(dtype)

    def carry(state, chunk_in):
        w_c, u_c, k_c, keep = chunk_in      # a chunk's, [b, K, r, ...]
        new = (w_c - jnp.einsum("bkrid,bkrde->bkrie", u_c,
                                state.astype(dtype),
                                preferred_element_type=_F32)).astype(dtype)
        added = jnp.einsum("bkrid,bkrie->bkrde", k_c, new,
                           preferred_element_type=_F32)
        return ((state * keep[..., None, None] + added).astype(state_dtype),
                (new, state))

    first = lambda t: jnp.moveaxis(t, 1, 0)
    _, (new, entering) = jax.lax.scan(
        carry, jnp.zeros((batch, key_heads, per_key, d_k, d_v), state_dtype),
        (first(w), first(u.astype(dtype)), first(k_out),
         first(jnp.exp(last))))
    new, entering = jnp.moveaxis(new, 0, 1), jnp.moveaxis(entering, 0, 1)
    inside = (qk[:, :, :, None] * decay).astype(dtype)
    o = jnp.einsum("bnkrij,bnkrjd->bnkrid", inside, new,
                   preferred_element_type=_F32)
    q_in = (by_key(q).astype(_F32) * jnp.exp(cum)[..., None]).astype(dtype)
    o = o + jnp.einsum("bnkrid,bnkrde->bnkrie", q_in, entering.astype(dtype),
                       preferred_element_type=_F32)
    o = jnp.transpose(o, (0, 1, 4, 2, 3, 5)).reshape(
        batch, seq + pad, value_heads, d_v)[:, :seq]
    return o.astype(dtype)


# ------------------------------------------------- the kernels' arithmetic

class _Plan(NamedTuple):
    """All a kernel call is built from besides its operands' shapes. Made
    outside the jitted calls, so that what the process holds besides the
    operands (the backend, the caller's constants) is part of their
    cache's key and never read under a cached trace."""
    chunk: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    key_block: int      # key heads a grid step takes
    state_dtype: Any    # what the sums, the system, its inverse and the
                        # carried state are rounded to (float32: not at all)
    precision: Any      # of the float32 products that invert the system
    interpret: bool


def _count_trace(kernel, plan):
    """Which kernels a job got, by the chunk and head widths, and which
    inverse: ``solved``, the width of the blocks taken by substitution."""
    _pallas.count_trace(
        "hvt_gdn_kernel_traces_total",
        "gated delta rule kernels traced into compiled programs "
        "(counted per trace, not per execution)",
        kernel=kernel, chunk=plan.chunk, key_dim=plan.key_dim,
        value_dim=plan.value_dim, solved=_solved(plan.chunk))


def _rounder(state_dtype):
    """What a builder's experiment that steers the state's dtype from
    outside (``gdn.STATE_DTYPE``) does to a float32 value in VMEM: rounds
    it there and back. Float32, the dtype the program runs, is no
    operation at all."""
    if jnp.dtype(state_dtype) == jnp.dtype(_F32):
        return lambda x: x
    return lambda x: x.astype(state_dtype).astype(_F32)


def _stored_as(state_dtype):
    """``_rounder`` for the XLA operations around the kernels, float32 out:
    there a conversion to ``state_dtype`` and back is dropped (XLA allows
    itself the excess precision), so the rounding is spelled out."""
    if jnp.dtype(state_dtype) == jnp.dtype(_F32):
        return lambda x: x.astype(_F32)
    info = jnp.finfo(state_dtype)
    return lambda x: jax.lax.reduce_precision(x.astype(_F32), info.nexp,
                                              info.nmant)


def _positions(c):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row, col


# The width of the diagonal blocks ``_unit_lower_inverse`` takes by
# substitution: two float32 sublane tiles a block, so the chunk's eight
# blocks side by side are two vregs. On a v5e the products that built
# blocks of 4, 8 and 16 were six of a chunk's eleven, each at a whole
# ``[128, 128]`` float32 product's fixed cost for a sliver of its work
# (PERF.md section 6, PR 63).
_SOLVED = 16


def _solved(c):
    """The width of the diagonal blocks that ``_unit_lower_inverse`` takes
    by substitution in a chunk of ``c`` positions, from the chunk alone:
    ``_SOLVED`` where the level above them works on whole blocks, 0 where
    every level is a product (the tests' small chunks). The label
    ``solved`` of both rules' trace counters."""
    return 0 if c % (2 * _SOLVED) else _SOLVED


def _substituted(n, row, col, low):
    """``(I + A)^-1`` for ``A`` the part of the strictly lower ``n [c,
    c]`` float32 inside its diagonal blocks of ``_SOLVED``: forward
    substitution in float32 on the VPU, **no product**. With ``a_r``
    column ``r`` of a block, ``I + A = (I + a_0 e_0^T) ... (I + a_14
    e_14^T)``, so the inverse is fifteen dependent rank-one updates ``X <-
    X - a_r (x) X[r, :]`` from ``X = I``, every block at once. The blocks
    lie side by side along the lanes, ``[_SOLVED, c]`` (block ``b`` at
    lanes ``16 b`` on: two vregs where the system is sixteen), so a step
    is one lane of each block spread over its sixteen (a lane gather a
    vreg, off the chain), row ``r`` spread over the sublanes (a masked
    sum), a multiply and a subtract. Exact float32 arithmetic, ``low``
    applied to every step's result. One traced step, unrolled where it is
    lowered (its index is a constant there), so the traced program does
    not go by the steps."""
    c = n.shape[0]
    s = _SOLVED
    # iotas of their own: Mosaic does not take a slice of one
    at = jax.lax.broadcasted_iota(jnp.int32, (s, c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s, c), 1)
    first = lane & -s                       # its block's first lane
    inside = (row & -s) == (col & -s)
    blocks = jnp.where(inside, n, 0.0)
    side_by_side = functools.reduce(
        jnp.add, [blocks[i:i + s] for i in range(0, c, s)])

    def step(r, x):
        column = jnp.take_along_axis(side_by_side, first + r, axis=1)
        x_r = jnp.sum(jnp.where(at == r, x, 0.0), axis=0, keepdims=True)
        return low(x - column * x_r)

    solved = jax.lax.fori_loop(0, s - 1, step,
                               (lane - first == at).astype(_F32),
                               unroll=True)
    return jnp.where(inside, jnp.concatenate([solved] * (c // s), axis=0),
                     0.0)


def _unit_lower_inverse(n, row, col, plan):
    """``(I + N)^-1`` for a strictly lower ``n [c, c]`` float32. The
    diagonal blocks of ``_SOLVED`` by substitution on the VPU
    (``_substituted``), then by blocks as ``unit_lower_inverse`` has it:
    ``T <- T - T O T`` with ``O`` the part of ``N`` in the lower left
    quarter of each ``2 m`` block, from ``m = _SOLVED``, float32 products
    at ``plan.precision``: three levels and six products at the kernels'
    chunk of 128, where doubling from ``m = 1`` made eleven. A chunk that
    twice ``_SOLVED`` does not divide takes every level by products from
    ``m = 1`` (``_solved``: from the chunk at trace time). ``T O T`` is
    zero outside the rows of the blocks' second halves, so where those are
    whole sublane tiles (``m`` a multiple of 8) only they go through the
    products (6% of the forward on a v5e, not the third their rows are:
    PERF.md section 6, PR 34)."""
    c = n.shape[0]
    low = _rounder(plan.state_dtype)
    dot32 = lambda a, b: jax.lax.dot_general(
        a, b, NN, precision=plan.precision, preferred_element_type=_F32)
    solved = _solved(c)
    if solved:
        inverse = _substituted(n, row, col, low)
        shift = solved.bit_length() - 1
    else:
        # m = 1: the blocks' inverses are the identity, so T O T is O itself
        inverse, shift = low((row == col).astype(_F32) - jnp.where(
            ((row ^ col) == 1) & ((row & 1) == 1), n, 0.0)), 1
    while (m := 1 << shift) < c:
        # rows in the second half of a block of 2 m, columns in its first
        quarter = ((((row ^ col) >> shift) == 1)
                   & (((row >> shift) & 1) == 1))
        lower_left = jnp.where(quarter, n, 0.0)
        if m % 8 or c % (2 * m):
            inverse = low(inverse - dot32(dot32(inverse, lower_left),
                                          inverse))
        else:
            blocks = [inverse[i:i + m] for i in range(0, c, m)]
            second = jnp.concatenate(blocks[1::2], axis=0)
            second = second - dot32(dot32(second, lower_left), inverse)
            blocks[1::2] = [second[i:i + m] for i in range(0, c // 2, m)]
            inverse = low(jnp.concatenate(blocks, axis=0))
        shift += 1
    return inverse


def _decay(g_col, g_row, row, col):
    """``exp(G_i - G_j)`` for ``j <= i``, 0 above the diagonal."""
    return jnp.where(row >= col,
                     jnp.exp(jnp.minimum(g_col - g_row, 0.0)), 0.0)


def _system_inverse(k, g_col, g_row, beta, row, col, plan):
    """``T = (I + N)^-1`` of one chunk and value head, float32: ``N_ij =
    beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i``."""
    low = _rounder(plan.state_dtype)
    system = jnp.where(row > col, low(
        beta * dot(k, k, NT) * _decay(g_col, g_row, row, col)), 0.0)
    return _unit_lower_inverse(system, row, col, plan)


class _Chunk(NamedTuple):
    """A chunk's forward quantities, for one value head: what the backward
    pass reads besides the operands."""
    o: Any          # [c, d_v] float32
    state: Any      # [d_k, d_v] float32, what the chunk hands on
    decay: Any      # exp(G_i - G_j), j <= i
    qk: Any
    grown: Any      # exp(G) [c, 1]
    v_in: Any       # beta v
    k_in: Any       # beta k exp(G)
    u: Any          # T k_in, in the operands' dtype
    new: Any        # the chunk's corrections, in the operands' dtype
    inside: Any     # (q k^T) decay, in the operands' dtype
    q_in: Any       # q exp(G)
    k_out: Any      # k exp(G_last - G)
    left: Any       # exp(G_last - G) [c, 1]
    kept: Any       # exp(G_last) [1, d_v]


def _chunk(state, q, k, v, g_col, g_row, beta, inverse, row, col, plan):
    """One chunk of one value head entered with ``state [d_k, d_v]``
    float32: ``q``, ``k`` ``[c, d_k]``, ``v [c, d_v]``, the inclusive sum
    of ``g`` as a column ``g_col [c, 1]`` and a row ``g_row [1, c]``,
    ``beta [c, 1]`` and the inverse of the chunk's system ``[c, c]`` in
    the operands' dtype."""
    dtype = v.dtype
    low = _rounder(plan.state_dtype)
    c = q.shape[0]
    decay = _decay(g_col, g_row, row, col)
    qk = dot(q, k, NT)
    grown = jnp.exp(g_col)
    k32 = k.astype(_F32)
    v_in = (v.astype(_F32) * beta).astype(dtype)
    k_in = (k32 * (beta * grown)).astype(dtype)
    w = dot(inverse, v_in, NN)
    u = dot(inverse, k_in, NN).astype(dtype)
    entered = state.astype(dtype)
    new = (w - dot(u, entered, NN)).astype(dtype)
    inside = (qk * decay).astype(dtype)
    q_in = (q.astype(_F32) * grown).astype(dtype)
    o = dot(inside, new, NN) + dot(q_in, entered, NN)
    # G_last as [1, 1]; Mosaic broadcasts along one of sublanes and lanes
    # at a time, so what scales the state is a row
    last = jnp.sum(jnp.where(col[:1] == c - 1, g_row, 0.0), axis=1,
                   keepdims=True)
    left = jnp.exp(last - g_col)
    kept = jnp.exp(jnp.broadcast_to(last, (1, state.shape[1])))
    k_out = (k32 * left).astype(dtype)
    handed = low(state * kept + dot(k_out, new, TN))
    return _Chunk(o, handed, decay, qk, grown, v_in, k_in, u, new, inside,
                  q_in, k_out, left, kept)


def _chunk_backward(at, state, q, k, v, g_col, g_row, beta, inverse, do,
                    d_handed, row, col, plan):
    """The transpose of ``_chunk`` at ``at`` (its forward quantities):
    from ``do [c, d_v]`` and ``d_handed [d_k, d_v]`` float32 to ``(dq, dk,
    dv, dg_col, dg_row, dbeta, d_state)``, all float32; ``dg_col [c, 1]``
    and ``dg_row [1, c]`` are the two halves of ``dG`` (what reached ``G``
    as a column and as a row). Products in the operands' dtype with
    float32 accumulation, as the forward's; the inverse's transpose is
    ``-T^T g T^T``, two float32 products at ``plan.precision``."""
    dtype = v.dtype
    c = q.shape[0]
    rows = lambda t: jnp.sum(t, axis=1, keepdims=True)      # [c, 1]
    dot32 = lambda a, b, dims: jax.lax.dot_general(
        a, b, dims, precision=plan.precision, preferred_element_type=_F32)
    q32, k32, v32 = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    entered = state.astype(dtype)
    do = do.astype(dtype)
    handed = d_handed.astype(dtype)

    # o = inside new + q_in S;  S' = kept S + k_out^T new
    d_inside = dot(do, at.new, NT)                        # [c, c]
    d_new = dot(at.inside, do, TN) + dot(at.k_out, handed, NN)
    d_q_in = dot(do, entered, NT)                         # [c, d_k]
    d_k_out = dot(at.new, handed, NT)                     # [c, d_k]
    # new = T v_in - u S
    d_new = d_new.astype(dtype)
    d_u = -dot(d_new, entered, NT)                        # [c, d_k]
    d_state = (d_handed * at.kept + dot(at.q_in, do, TN)
               - dot(at.u, d_new, TN))
    d_u = d_u.astype(dtype)
    d_inverse = dot(d_new, at.v_in, NT) + dot(d_u, at.k_in, NT)
    d_v_in = dot(inverse, d_new, TN)                      # [c, d_v]
    d_k_in = dot(inverse, d_u, TN)                        # [c, d_k]
    # T = (I + N)^-1
    inverse32 = inverse.astype(_F32)
    d_system = jnp.where(row > col, -dot32(
        inverse32, dot32(d_inverse, inverse32, NT), TN), 0.0)
    # N = beta kk decay (strictly lower);  inside = qk decay
    kk = dot(k, k, NT)
    d_kk = d_system * beta * at.decay
    d_qk = d_inside * at.decay
    d_decay = d_system * beta * kk + d_inside * at.qk
    d_lag = d_decay * at.decay                  # decay = exp(G_i - G_j)
    d_kk, d_qk = d_kk.astype(dtype), d_qk.astype(dtype)
    dq = dot(d_qk, k, NN) + d_q_in * at.grown
    dk = (dot(d_kk, k, NN) + dot(d_kk, k, TN) + dot(d_qk, q, TN)
          + d_k_in * (beta * at.grown) + d_k_out * at.left)
    dv = d_v_in * beta
    # k_in = k beta exp(G), v_in = v beta, q_in = q exp(G),
    # k_out = k exp(G_last - G)
    d_scale = rows(d_k_in * k32)                # of beta exp(G)
    d_left = rows(d_k_out * k32) * at.left      # of G_last - G
    dbeta = (rows(d_system * kk * at.decay) + d_scale * at.grown
             + rows(d_v_in * v32))
    dg_col = (rows(d_lag) + (d_scale * beta + rows(d_q_in * q32)) * at.grown
              - d_left)
    d_last = (jnp.sum(d_left, axis=0, keepdims=True) + jnp.sum(
        at.kept * jnp.sum(d_handed * state, axis=0, keepdims=True), axis=1,
        keepdims=True))
    dg_row = (jnp.where(col[:1] == c - 1, d_last, 0.0)
              - jnp.sum(d_lag, axis=0, keepdims=True))
    return dq, dk, dv, dg_col, dg_row, dbeta, d_state


# ---------------------------------------------------------------- kernels
#
# Grid (batch, chunk, block of key heads), the heads innermost, so that
# the [c, H_v] tiles of G, beta and their gradients stay where they are
# while the heads pass and each head writes its own column. A step takes
# ``plan.key_block`` key heads with all the value heads they serve: q and
# k are read once, dq and dk summed before they leave. A chunk's system
# does not depend on the state that enters the chunk, so its inverse has a
# kernel of its own with nothing to carry (``hvt_gdn_inverse``); the
# forward and backward kernels walk the chunk axis in order (the
# backward's index maps turn it round) with the states in scratch.

_HEADS_A_STEP = 4   # value heads: on a v5e at 2 x 8192, 32 heads of 128 x
                    # 128 on 16, 2, 4 and 8 a step gave a forward of 13.5,
                    # 13.2 and 13.0 ms (PERF.md section 6, PR 34)


def _key_block(key_heads, per_key):
    most = max(_HEADS_A_STEP // per_key, 1)
    return max(n for n in range(1, most + 1) if key_heads % n == 0)


def _step_heads(plan, kh):
    """``(j, h, key, at_k, at_v)`` of each value head a grid step takes:
    its place in the step's blocks, its index among all value heads, its
    key head's place in the step and the two column slices."""
    per_key = plan.value_heads // plan.key_heads
    d_k, d_v = plan.key_dim, plan.value_dim
    first = kh * (plan.key_block * per_key)
    return [(j, first + j, j // per_key,
             slice(j // per_key * d_k, (j // per_key + 1) * d_k),
             slice(j * d_v, (j + 1) * d_v))
            for j in range(plan.key_block * per_key)]


def _gates(g_col_ref, g_row_ref, beta_ref, heads):
    """``(g_col [c, 1], g_row [1, c], beta [c, 1])`` of each of a step's
    value heads, from the ``[c, H_v]`` and ``[H_v, c]`` tiles."""
    g_cols, betas = g_col_ref[0], beta_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, g_cols.shape, 1)
    column = lambda tile, h: jnp.sum(jnp.where(lane == h, tile, 0.0),
                                     axis=1, keepdims=True)
    return [(column(g_cols, h), g_row_ref[0, pl.ds(h, 1), :],
             column(betas, h)) for _, h, *_ in heads], lane


def _inverse_kernel(k_ref, g_col_ref, g_row_ref, beta_ref, inverse_ref, *,
                    plan):
    heads = _step_heads(plan, pl.program_id(2))
    row, col = _positions(plan.chunk)
    gates, _ = _gates(g_col_ref, g_row_ref, beta_ref, heads)
    done = [_system_inverse(k_ref[0, :, at_k], *gate, row, col, plan)
            for gate, (*_, at_k, _) in zip(gates, heads)]
    for inverse, (j, *_) in zip(done, heads):
        inverse_ref[0, 0, j] = inverse.astype(inverse_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_col_ref, g_row_ref, beta_ref,
                inverse_ref, o_ref, entering_ref, state_ref, *, plan):
    ni, kh = pl.program_id(1), pl.program_id(2)
    heads = _step_heads(plan, kh)
    row, col = _positions(plan.chunk)
    gates, _ = _gates(g_col_ref, g_row_ref, beta_ref, heads)

    @pl.when(ni == 0)
    def _first_chunk():
        for _, h, *_ in heads:
            state_ref[h] = jnp.zeros(state_ref.shape[1:], _F32)

    states = [state_ref[h] for _, h, *_ in heads]
    done = [_chunk(state, q_ref[0, :, at_k], k_ref[0, :, at_k],
                   v_ref[0, :, at_v], *gate, inverse_ref[0, 0, j], row, col,
                   plan)
            for state, gate, (j, _, _, at_k, at_v)
            in zip(states, gates, heads)]
    for state, at, (j, h, _, _, at_v) in zip(states, done, heads):
        entering_ref[0, 0, j] = state
        o_ref[0, :, at_v] = at.o.astype(o_ref.dtype)
        state_ref[h] = at.state


def _bwd_kernel(q_ref, k_ref, v_ref, g_col_ref, g_row_ref, beta_ref,
                inverse_ref, entering_ref, do_ref, dq_ref, dk_ref, dv_ref,
                dg_col_ref, dg_row_ref, dbeta_ref, d_state_ref, *, plan):
    ni, kh = pl.program_id(1), pl.program_id(2)
    heads = _step_heads(plan, kh)
    row, col = _positions(plan.chunk)
    gates, lane = _gates(g_col_ref, g_row_ref, beta_ref, heads)

    @pl.when(kh == 0)
    def _first_heads():
        dg_col_ref[...] = jnp.zeros_like(dg_col_ref)
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    @pl.when(ni == 0)               # the last chunk: nothing comes after
    def _last_chunk():
        for _, h, *_ in heads:
            d_state_ref[h] = jnp.zeros(d_state_ref.shape[1:], _F32)

    def one(gate, j, h, at_k, at_v):
        operands = (entering_ref[0, 0, j], q_ref[0, :, at_k],
                    k_ref[0, :, at_k], v_ref[0, :, at_v], *gate,
                    inverse_ref[0, 0, j])
        return _chunk_backward(
            _chunk(*operands, row, col, plan), *operands,
            do_ref[0, :, at_v], d_state_ref[h], row, col, plan)

    done = [one(gate, j, h, at_k, at_v)
            for gate, (j, h, _, at_k, at_v) in zip(gates, heads)]
    for key in range(plan.key_block):
        at_k = slice(key * plan.key_dim, (key + 1) * plan.key_dim)
        of_key = [d for d, head in zip(done, heads) if head[2] == key]
        dq_ref[0, :, at_k] = sum(d[0] for d in of_key).astype(dq_ref.dtype)
        dk_ref[0, :, at_k] = sum(d[1] for d in of_key).astype(dk_ref.dtype)
    dg_cols, dbetas = dg_col_ref[0], dbeta_ref[0]
    for (_, _, dv, dg_col, dg_row, dbeta, d_state), (
            _, h, _, _, at_v) in zip(done, heads):
        dv_ref[0, :, at_v] = dv.astype(dv_ref.dtype)
        dg_cols = jnp.where(lane == h, dg_col, dg_cols)
        dbetas = jnp.where(lane == h, dbeta, dbetas)
        dg_row_ref[0, pl.ds(h, 1), :] = dg_row
        d_state_ref[h] = d_state
    dg_col_ref[0], dbeta_ref[0] = dg_cols, dbetas


def _specs(plan, chunk_of):
    """Block specs of a call's operands by kind; ``chunk_of(ni)`` is the
    chunk the grid's step ``ni`` works on."""
    c = plan.chunk
    values = plan.key_block * (plan.value_heads // plan.key_heads)
    per_head = lambda *tile: pl.BlockSpec(
        (1, 1, values, *tile),
        lambda bi, ni, kh: (bi, chunk_of(ni), kh, 0, 0))
    return {
        "key": pl.BlockSpec((1, c, plan.key_block * plan.key_dim),
                            lambda bi, ni, kh: (bi, chunk_of(ni), kh)),
        "value": pl.BlockSpec((1, c, values * plan.value_dim),
                              lambda bi, ni, kh: (bi, chunk_of(ni), kh)),
        "column": pl.BlockSpec((1, c, plan.value_heads),
                               lambda bi, ni, kh: (bi, chunk_of(ni), 0)),
        "row": pl.BlockSpec((1, plan.value_heads, c),
                            lambda bi, ni, kh: (bi, 0, chunk_of(ni))),
        "state": per_head(plan.key_dim, plan.value_dim),
        "inverse": per_head(c, c),
    }


def _call(kernel, name, plan, operands, in_specs, out_specs, out_shape, *,
          carries):
    """One of the three Pallas calls; ``operands`` are ``[b, s, ...]``.
    ``carries``: the kernel keeps a state a value head in scratch from one
    chunk to the next."""
    batch, seq = operands[0].shape[:2]
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid=(batch, seq // plan.chunk, plan.key_heads // plan.key_block),
        in_specs=in_specs, out_specs=out_specs,
        out_shape=[_pallas.out(shape, dtype, *operands)
                   for shape, dtype in out_shape],
        scratch_shapes=[pltpu.VMEM(
            (plan.value_heads, plan.key_dim, plan.value_dim), _F32)]
        if carries else [],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", *(("arbitrary",) * 2 if carries
                          else ("parallel",) * 2))),
        interpret=plan.interpret, name=name)(*operands)


def _per_head(plan, operand, *tile):
    batch, seq = operand.shape[:2]
    return (batch, seq // plan.chunk, plan.value_heads, *tile)


# Each call is a ``jax.jit`` of its own, as the flash kernels' are: a
# model's layers share one trace and one lowered function a kernel.
@functools.partial(jax.jit, static_argnames=("plan", "dtype"))
def _inverse_call(k, g_col, beta, *, plan, dtype):
    """``k [b, s, H_k d_k]``, ``g_col`` (the inclusive sum of ``g`` inside
    each chunk) and ``beta`` ``[b, s, H_v]`` float32, ``s`` a multiple of
    the chunk -> the inverse of each chunk's and value head's system,
    ``[b, n, H_v, c, c]``, made in float32 and written in ``dtype``, the
    one the products take it in."""
    _count_trace("inverse", plan)
    spec = _specs(plan, lambda ni: ni)
    return _call(
        _inverse_kernel, "hvt_gdn_inverse", plan,
        (k, g_col, jnp.swapaxes(g_col, 1, 2), beta),
        [spec["key"], spec["column"], spec["row"], spec["column"]],
        [spec["inverse"]],
        [(_per_head(plan, k, plan.chunk, plan.chunk), dtype)],
        carries=False)[0]


@functools.partial(jax.jit, static_argnames="plan")
def _fwd_call(q, k, v, g_col, beta, inverse, *, plan):
    """``q``, ``k`` ``[b, s, H_k d_k]``, ``v [b, s, H_v d_v]``, ``g_col``,
    ``beta`` and ``inverse`` as ``_inverse_call`` has them -> ``o`` like
    ``v`` and the state each chunk and value head was entered with, ``[b,
    n, H_v, d_k, d_v]`` float32, which the backward pass reads."""
    _count_trace("fwd", plan)
    spec = _specs(plan, lambda ni: ni)
    return _call(
        _fwd_kernel, "hvt_gdn_fwd", plan,
        (q, k, v, g_col, jnp.swapaxes(g_col, 1, 2), beta, inverse),
        [spec["key"], spec["key"], spec["value"], spec["column"],
         spec["row"], spec["column"], spec["inverse"]],
        [spec["value"], spec["state"]],
        [(v.shape, v.dtype),
         (_per_head(plan, v, plan.key_dim, plan.value_dim), _F32)],
        carries=True)


@functools.partial(jax.jit, static_argnames="plan")
def _bwd_call(q, k, v, g_col, beta, inverse, entering, do, *, plan):
    """``(dq, dk, dv, dG, dbeta)`` for ``_fwd_call``'s operands with the
    inverse a function of them, ``dq`` and ``dk`` summed over the value
    heads of a key head, ``dG`` and ``dbeta`` ``[b, s, H_v]`` float32."""
    _count_trace("bwd", plan)
    chunks = v.shape[1] // plan.chunk
    spec = _specs(plan, lambda ni: chunks - 1 - ni)
    column = (g_col.shape, _F32)
    dq, dk, dv, dg_col, dg_row, dbeta = _call(
        _bwd_kernel, "hvt_gdn_bwd", plan,
        (q, k, v, g_col, jnp.swapaxes(g_col, 1, 2), beta, inverse, entering,
         do),
        [spec["key"], spec["key"], spec["value"], spec["column"],
         spec["row"], spec["column"], spec["inverse"], spec["state"],
         spec["value"]],
        [spec["key"], spec["key"], spec["value"], spec["column"],
         spec["row"], spec["column"]],
        [(q.shape, q.dtype), (k.shape, k.dtype), (v.shape, v.dtype), column,
         ((v.shape[0], plan.value_heads, v.shape[1]), _F32), column],
        carries=True)
    return dq, dk, dv, dg_col + jnp.swapaxes(dg_row, 1, 2), dbeta


# The name (``jax.ad_checkpoint.checkpoint_name``) of the chunks'
# inverses, for a caller that recomputes its forward pass and would keep
# them: they are two thirds of the forward's time and do not depend on the
# carried state, at ``c`` bf16 numbers a position and value head (134 MB a
# layer of 2 x 8192 x 32), so ``models.GPT`` keeps them under ``remat``.
KEPT_INVERSE = "gdn_rule_inverse"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g_col, beta, plan):
    return _rule_fwd(q, k, v, g_col, beta, plan)[0]


def _rule_fwd(q, k, v, g_col, beta, plan):
    inverse = checkpoint_name(
        _inverse_call(k, g_col, beta, plan=plan, dtype=v.dtype),
        KEPT_INVERSE)
    o, entering = _fwd_call(q, k, v, g_col, beta, inverse, plan=plan)
    return o, (q, k, v, g_col, beta, inverse, entering)


def _rule_bwd(plan, res, do):
    return _bwd_call(*res, do, plan=plan)


_rule.defvjp(_rule_fwd, _rule_bwd)


def _prepare(q, k, v, g, beta, chunk, state_dtype, precision):
    batch, seq, key_heads, d_k = q.shape
    value_heads, d_v = v.shape[-2:]
    if value_heads % key_heads:
        raise ValueError(
            f"{value_heads} value heads over {key_heads} key heads: a key "
            f"head serves a whole number of value heads")
    pad = -seq % chunk
    if pad:
        grow = lambda t: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
    flat = lambda t: t.reshape(batch, seq + pad, -1)
    stored = _stored_as(state_dtype)
    # G_i, the inclusive sum of g inside each chunk
    g_col = stored(jnp.cumsum(
        stored(g).reshape(batch, -1, chunk, value_heads),
        axis=2).reshape(batch, seq + pad, value_heads))
    beta = stored(beta)
    plan = _Plan(chunk, key_heads, value_heads, d_k, d_v,
                 _key_block(key_heads, value_heads // key_heads),
                 jnp.dtype(state_dtype), precision, _pallas.interpret())
    return plan, (flat(q), flat(k), flat(v), g_col, beta), pad


def gated_delta_rule_kernels(q, k, v, g, beta, *, chunk, state_dtype=_F32,
                             precision=_HIGHEST):
    """``gated_delta_rule_plain`` through the kernels: its arguments (the
    chunk named) and its result. Differentiable in all five. A sequence
    the chunk does not divide is padded with positions whose ``g`` and
    ``beta`` are 0."""
    seq = q.shape[1]
    plan, operands, pad = _prepare(q, k, v, g, beta, chunk, state_dtype,
                                   precision)
    return _rule(*operands, plan).reshape(
        v.shape[0], seq + pad, *v.shape[2:])[:, :seq]
