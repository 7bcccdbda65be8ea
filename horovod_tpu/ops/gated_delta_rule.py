"""The chunked gated delta rule (``models/gdn.py``) as three Pallas TPU
kernels under one custom VJP.

In plain ``jax.numpy`` a chunk's ``[c, c]`` float32 matrices (the decay
mask, ``k k^T``, ``q k^T``, the triangular system, its inverse and that
inverse's intermediates) each pass through HBM, the operands are copied
between ``[b, s, h, d]`` and a chunked layout, and the carry over the
chunks is an XLA loop of small batched products. Here a chunk of one value
head is a few hundred KB of VMEM and none of that exists outside it:

- ``hvt_gdn_inverse`` forms a chunk's decays and system in registers and
  inverts it by blocks. It depends on no state, so it carries nothing and a
  caller that recomputes its forward pass can keep its result
  (``KEPT_INVERSE``) and not run it twice.
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

The equations are ``models/gdn.py``'s (``gated_delta_rule_plain`` there is
this module's reference and the path every other shape and backend
takes): the decays, their sums and exponents, the system, its inverse and
that inverse's products are float32 at the caller's precision, the carried
``S`` and ``dS`` are float32, the other products run in the operands' dtype
and accumulate in float32; every exponent is of a non-positive number.
The cumulative sum of ``g`` inside a chunk and its transpose for ``dg`` are
XLA operations around the kernels (``[b, s, H_v]`` float32).

On the CPU the same kernel code runs through the Pallas interpreter, at
any chunk and head width; compiled, Mosaic wants a chunk and head widths
that are multiples of 128 (``models/gdn.py`` sends it nothing else).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.flash_attention import _NN, _NT, _TN, _interpret, _out

_F32 = jnp.float32


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
    """The engagement counter: which kernels a job got, by the chunk and
    head widths. Trace-time Python only."""
    try:
        from horovod_tpu import metrics

        metrics.counter(
            "hvt_gdn_kernel_traces_total",
            "gated delta rule kernels traced into compiled programs "
            "(counted per trace, not per execution)",
            ("kernel", "chunk", "key_dim", "value_dim"),
        ).labels(kernel=kernel, chunk=str(plan.chunk),
                 key_dim=str(plan.key_dim),
                 value_dim=str(plan.value_dim)).inc()
    except Exception:
        pass  # telemetry must never break a trace


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


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


def _unit_lower_inverse(n, row, col, plan):
    """``(I + N)^-1`` for a strictly lower ``n [c, c]`` float32, by blocks
    as ``gdn.unit_lower_inverse`` has it: ``T <- T - T O T`` with ``O`` the
    part of ``N`` in the lower left quarter of each ``2 m`` block, from
    ``m = 1``; float32 products at ``plan.precision``. ``T O T`` is zero
    outside the rows of the blocks' second halves, so where those are
    whole sublane tiles (``m`` a multiple of 8) only they go through the
    products (6% of the forward on a v5e, not the third their rows are:
    PERF.md section 6, PR 34)."""
    c = n.shape[0]
    low = _rounder(plan.state_dtype)
    dot32 = lambda a, b: jax.lax.dot_general(
        a, b, _NN, precision=plan.precision, preferred_element_type=_F32)
    # m = 1: the blocks' inverses are the identity, so T O T is O itself
    inverse = low((row == col).astype(_F32) - jnp.where(
        ((row ^ col) == 1) & ((row & 1) == 1), n, 0.0))
    shift = 1
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
        beta * _dot(k, k, _NT) * _decay(g_col, g_row, row, col)), 0.0)
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
    qk = _dot(q, k, _NT)
    grown = jnp.exp(g_col)
    k32 = k.astype(_F32)
    v_in = (v.astype(_F32) * beta).astype(dtype)
    k_in = (k32 * (beta * grown)).astype(dtype)
    w = _dot(inverse, v_in, _NN)
    u = _dot(inverse, k_in, _NN).astype(dtype)
    entered = state.astype(dtype)
    new = (w - _dot(u, entered, _NN)).astype(dtype)
    inside = (qk * decay).astype(dtype)
    q_in = (q.astype(_F32) * grown).astype(dtype)
    o = _dot(inside, new, _NN) + _dot(q_in, entered, _NN)
    # G_last as [1, 1]; Mosaic broadcasts along one of sublanes and lanes
    # at a time, so what scales the state is a row
    last = jnp.sum(jnp.where(col[:1] == c - 1, g_row, 0.0), axis=1,
                   keepdims=True)
    left = jnp.exp(last - g_col)
    kept = jnp.exp(jnp.broadcast_to(last, (1, state.shape[1])))
    k_out = (k32 * left).astype(dtype)
    handed = low(state * kept + _dot(k_out, new, _TN))
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
    d_inside = _dot(do, at.new, _NT)                        # [c, c]
    d_new = _dot(at.inside, do, _TN) + _dot(at.k_out, handed, _NN)
    d_q_in = _dot(do, entered, _NT)                         # [c, d_k]
    d_k_out = _dot(at.new, handed, _NT)                     # [c, d_k]
    # new = T v_in - u S
    d_new = d_new.astype(dtype)
    d_u = -_dot(d_new, entered, _NT)                        # [c, d_k]
    d_state = (d_handed * at.kept + _dot(at.q_in, do, _TN)
               - _dot(at.u, d_new, _TN))
    d_u = d_u.astype(dtype)
    d_inverse = _dot(d_new, at.v_in, _NT) + _dot(d_u, at.k_in, _NT)
    d_v_in = _dot(inverse, d_new, _TN)                      # [c, d_v]
    d_k_in = _dot(inverse, d_u, _TN)                        # [c, d_k]
    # T = (I + N)^-1
    inverse32 = inverse.astype(_F32)
    d_system = jnp.where(row > col, -dot32(
        inverse32, dot32(d_inverse, inverse32, _NT), _TN), 0.0)
    # N = beta kk decay (strictly lower);  inside = qk decay
    kk = _dot(k, k, _NT)
    d_kk = d_system * beta * at.decay
    d_qk = d_inside * at.decay
    d_decay = d_system * beta * kk + d_inside * at.qk
    d_lag = d_decay * at.decay                  # decay = exp(G_i - G_j)
    d_kk, d_qk = d_kk.astype(dtype), d_qk.astype(dtype)
    dq = _dot(d_qk, k, _NN) + d_q_in * at.grown
    dk = (_dot(d_kk, k, _NN) + _dot(d_kk, k, _TN) + _dot(d_qk, q, _TN)
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
        out_shape=[_out(shape, dtype, *operands)
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
                 jnp.dtype(state_dtype), precision, _interpret())
    return plan, (flat(q), flat(k), flat(v), g_col, beta), pad


def gated_delta_rule(q, k, v, g, beta, *, chunk, state_dtype=_F32,
                     precision=jax.lax.Precision.HIGHEST):
    """``models/gdn.py``'s ``gated_delta_rule`` through the kernels:
    ``q``, ``k`` ``[batch, s, H_k, d_k]``, ``v [batch, s, H_v, d_v]``,
    ``g`` and ``beta`` ``[batch, s, H_v]`` float32 (``g <= 0``) -> ``o``
    like ``v``. Differentiable in all five. A sequence the chunk does not
    divide is padded with positions whose ``g`` and ``beta`` are 0.
    ``state_dtype`` and ``precision`` are the caller's constants
    (``gdn.STATE_DTYPE``, ``gdn.INVERSE_PRECISION``)."""
    seq = q.shape[1]
    plan, operands, pad = _prepare(q, k, v, g, beta, chunk, state_dtype,
                                   precision)
    return _rule(*operands, plan).reshape(
        v.shape[0], seq + pad, *v.shape[2:])[:, :seq]
