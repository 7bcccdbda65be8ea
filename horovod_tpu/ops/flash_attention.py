"""Fused flash attention as a Pallas TPU kernel, with custom VJP.

The attention score matrix is the one intermediate XLA cannot fuse away on
its own; materializing it is O(S²) HBM traffic, which caps MXU utilization
at long context. This kernel keeps the [block_q × block_k] score tile in
VMEM, maintains online-softmax running (max, sum) statistics, and writes
only the O(S·D) output — the standard FlashAttention-2 decomposition, laid
out for the MXU (128×128 tiles, fp32 accumulation, bf16 operands).

Backward pass recomputes score tiles (FLOPs-for-HBM trade, the same choice
``jax.checkpoint`` makes) in two kernels: one gridded over Q blocks (dQ),
one over K/V blocks (dK, dV), using the saved logsumexp.

No reference-framework counterpart (Horovod ships gradients, not kernels);
this is part of the TPU framework's compute path. On the CPU the same
kernel code runs through the Pallas interpreter (the CPU has no Mosaic
compiler), so the CPU test mesh exercises it; on any other platform the
kernel is compiled and a compiler refusal propagates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128  # TPU vector lane width: scratch statistics are stored
              # broadcast across a full lane tile

# Crossover captured by an earlier builder on another rig, not
# reproduced: einsum ahead at seq<=2048, flash from 4096 up
FLASH_AUTO_THRESHOLD = 2048


def resolve_flash(use_flash, local_seq) -> bool:
    """Resolve a ``use_flash`` policy ("auto" | bool) for a given LOCAL
    sequence length (a static trace-time shape, so the choice compiles
    away). "auto" upgrades to flash only on a real TPU backend — the
    crossover was measured there, and off-TPU the kernel runs in pallas
    interpret mode, far slower than einsum.

    ``local_seq`` must be the length the attention actually runs over:
    the global length on a single device, the per-shard block length
    under the ring schedule. The shard functions in
    ``parallel/sequence.py`` resolve it themselves from their local
    (post-shard_map) shapes, where it is unambiguous (ADVICE r4)."""
    if isinstance(use_flash, str):
        if use_flash != "auto":
            raise ValueError(
                f"use_flash must be True, False, or 'auto'; got "
                f"{use_flash!r}")
        return (local_seq > FLASH_AUTO_THRESHOLD
                and jax.default_backend() == "tpu")
    return bool(use_flash)


def _interpret() -> bool:
    # only the CPU interprets (it has no Mosaic compiler; the CPU test
    # mesh runs the same kernel code)
    return jax.default_backend() == "cpu"


# Two-level decomposition: the sequence operand STREAMS through the
# grid's sequential LAST axis in large VMEM TILES (so per-kernel VMEM is
# O(tile), never O(seq) — a full-sequence-resident design exceeds the
# 16 MB scoped-VMEM limit at seq 8192), while INSIDE the kernel a
# fori_loop walks 128-wide sub-blocks of the tile with fine-grained
# causal skipping (one block per grid step pays per-step pipeline
# overhead plus DMA of fully-masked blocks). Online-softmax statistics
# live in VMEM scratch across the tile axis.


def _causal_n_eff(qi, block_q, ti, tile, block_k, n_sub):
    """Number of k sub-blocks of this tile a causal Q block attends to
    (sub-blocks entirely above the diagonal are skipped, same 128-block
    granularity as the resident design). Shared by the fwd and dQ
    kernels; the dkv kernel uses the dual (`start`) form."""
    return jnp.clip(
        ((qi + 1) * block_q - ti * tile + block_k - 1) // block_k,
        0, n_sub)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                l_ref, *, scale, causal, block_k):
    block_q = q_ref.shape[2]
    tile = k_ref.shape[2]
    qi = pl.program_id(2)
    ti = pl.program_id(3)
    n_t = pl.num_programs(3)
    q = q_ref[0, 0]                                   # [block_q, D]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _tile():
        def body(j, carry):
            acc, m, l = carry
            k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, bk]
            if causal:
                k_pos = (ti * tile + j * block_k
                         + jax.lax.iota(jnp.int32, block_k))
                sc = jnp.where(k_pos[None, :] <= q_pos[:, None], sc,
                               _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[:, None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc_new, m_new, l_new

        n_sub = tile // block_k
        n_eff = (_causal_n_eff(qi, block_q, ti, tile, block_k, n_sub)
                 if causal else n_sub)
        acc, m, l = jax.lax.fori_loop(
            0, n_eff, body, (acc_ref[...], m_ref[:, 0], l_ref[:, 0]))
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l[:, None], l_ref.shape)

    if causal:
        # tiles entirely above the diagonal still stream past (the
        # pipeline fetches every grid step) but do no MXU work
        pl.when(ti * tile < (qi + 1) * block_q)(_tile)
    else:
        _tile()

    @pl.when(ti == n_t - 1)
    def _finalize():
        m = m_ref[:, 0]
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :, 0] = m + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, scale, causal, block_k):
    block_q = q_ref.shape[2]
    tile = k_ref.shape[2]
    qi = pl.program_id(2)
    ti = pl.program_id(3)     # K/V tiles stream
    n_t = pl.num_programs(3)
    q = q_ref[0, 0]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]

    @pl.when(ti == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _tile():
        def body(j, dq):
            k = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                k_pos = (ti * tile + j * block_k
                         + jax.lax.iota(jnp.int32, block_k))
                sc = jnp.where(k_pos[None, :] <= q_pos[:, None], sc,
                               _NEG_INF)
            p = jnp.exp(sc - lse[:, None])
            dp = jax.lax.dot_general(
                do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None])
            return dq + jax.lax.dot_general(
                ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        n_sub = tile // block_k
        n_eff = (_causal_n_eff(qi, block_q, ti, tile, block_k, n_sub)
                 if causal else n_sub)
        dq_acc_ref[...] = jax.lax.fori_loop(0, n_eff, body,
                                            dq_acc_ref[...])

    if causal:
        pl.when(ti * tile < (qi + 1) * block_q)(_tile)
    else:
        _tile()

    @pl.when(ti == n_t - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale, causal,
                block_q):
    block_k = k_ref.shape[2]
    tile = q_ref.shape[2]
    ki = pl.program_id(2)
    ti = pl.program_id(3)     # Q/dO/lse/delta tiles stream
    n_t = pl.num_programs(3)
    k = k_ref[0, 0]                                   # [block_k, D]
    v = v_ref[0, 0]
    k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)

    @pl.when(ti == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _tile():
        def body(i, carry):
            dk, dv = carry
            q = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
            do = do_ref[0, 0, pl.ds(i * block_q, block_q),
                        :].astype(jnp.float32)
            lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), 0]
            delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), 0]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                q_pos = (ti * tile + i * block_q
                         + jax.lax.iota(jnp.int32, block_q))
                sc = jnp.where(k_pos[None, :] <= q_pos[:, None], sc,
                               _NEG_INF)
            p = jnp.exp(sc - lse[:, None])         # [bq, bk]
            dv_new = dv + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None])
            dk_new = dk + jax.lax.dot_general(
                ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            return dk_new, dv_new

        n_sub = tile // block_q
        if causal:
            # Q sub-blocks strictly before this K block see nothing
            start = jnp.clip((ki * block_k - ti * tile) // block_q,
                             0, n_sub)
        else:
            start = 0
        dk, dv = jax.lax.fori_loop(
            start, n_sub, body, (dk_acc_ref[...], dv_acc_ref[...]))
        dk_acc_ref[...] = dk
        dv_acc_ref[...] = dv

    if causal:
        # tiles whose every Q position precedes this K block are skipped
        pl.when((ti + 1) * tile > ki * block_k)(_tile)
    else:
        _tile()

    @pl.when(ti == n_t - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _blocks(s, requested):
    b = min(requested, s)
    while s % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, out_dtype):
    """Differentiable (o, lse). The lse output carries its own gradient:
    d lse/dS = P, so a dlse cotangent folds into the backward kernels as
    delta := rowsum(do∘o) − dlse — the kernels are unchanged."""
    o, lse = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                             out_dtype)
    return o, lse


# The dkv backward kernel carries more per-tile state than the forward
# (Q + dO tiles streamed together plus two fp32 accumulators), so the
# largest tile that fits the 16 MB scoped-VMEM limit is SMALLER there
# (tile 8192 in dkv overflowing VMEM was captured by an earlier builder
# on another rig, not reproduced). Cap dkv's tile independently so a
# user-requested HVT_FLASH_SEQ_TILE=8192 degrades only the one kernel
# that needs it.
_DKV_TILE_CAP = 4096


def _seq_tile(s, block_q, block_k, cap=None):
    """Streamed-sequence VMEM tile (elements of the seq axis per grid
    step). The default of 4096 was chosen by an earlier builder on
    another rig (d=64, 12 heads) and has not been reproduced. Override
    with HVT_FLASH_SEQ_TILE for other head dims; ``cap`` bounds the
    request per-kernel (the dkv backward caps at ``_DKV_TILE_CAP``).

    The tile must divide ``s`` AND be a multiple of both block sizes —
    the kernels walk ``tile // block`` sub-blocks, so a remainder would
    silently drop sequence positions. Both blocks divide s (``_blocks``),
    hence lcm(block_q, block_k) divides s and a valid tile always
    exists."""
    import math
    import os

    req = min(int(os.environ.get("HVT_FLASH_SEQ_TILE", "4096")), s)
    if cap is not None:
        req = min(req, cap)
    base = math.lcm(block_q, block_k)
    best, m = base, 2
    while m * base <= req:
        if s % (m * base) == 0:
            best = m * base
        m += 1
    if cap is not None and best > cap:
        # correctness pins the tile to >= lcm(block_q, block_k); block
        # sizes whose lcm exceeds the cap force a tile the capped
        # kernel may not fit in VMEM — say so instead of failing later
        # with an opaque scoped-VMEM allocation error
        import sys

        print(f"# horovod_tpu flash: block sizes ({block_q}, {block_k}) "
              f"force tile {best} > VMEM cap {cap} in the capped "
              f"backward kernel; expect scoped-VMEM pressure — use "
              f"blocks with lcm <= {cap}", file=sys.stderr)
    return best


def _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k, out_dtype):
    b, h, s, d = q.shape
    # Grouped-query attention is served ZERO-COPY: query head hi reads
    # K/V head hi // group through the block index map — no repeat
    # materialization, and the shared K/V tile stays VMEM-resident
    # across the group's consecutive hi grid steps.
    group = h // k.shape[1]
    # K/V stream through the grid's sequential LAST axis in VMEM tiles;
    # scratch accumulators carry the online softmax across tiles
    tile = _seq_tile(s, block_q, block_k)
    grid = (b, h, s // block_q, s // tile)
    qspec = pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ti: (bi, hi, qi, 0))
    kvspec = pl.BlockSpec((1, 1, tile, d),
                          lambda bi, hi, qi, ti: (bi, hi // group, ti, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k),
        grid=grid,
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, block_q, 1),
                                lambda bi, hi, qi, ti: (bi, hi, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, out_dtype),
                   jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=_interpret(),
        name="hvt_flash_fwd",
    )(q, k, v)
    return o, lse


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, out_dtype):
    o, lse = _flash_fwd_impl(q, k, v, scale, causal, block_q, block_k,
                             out_dtype)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, out_dtype, res, cot):
    do, dlse = cot
    q, k, v, o, lse = res
    b, h, s, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # [B, H, S, 1]
    # lse cotangent: ds gains + P∘dlse, i.e. delta shifts by −dlse
    delta = delta - dlse.astype(jnp.float32)

    # dq: grid (b, h, qi, ti) — K/V tiles stream past each Q block.
    # GQA reads the shared K/V head zero-copy via the index map.
    group = h // k.shape[1]
    tile = _seq_tile(s, block_q, block_k)
    q_by_qi = pl.BlockSpec((1, 1, block_q, d),
                           lambda bi, hi, qi, ti: (bi, hi, qi, 0))
    kv_tile = pl.BlockSpec((1, 1, tile, d),
                           lambda bi, hi, qi, ti: (bi, hi // group, ti, 0))
    vec_by_qi = pl.BlockSpec((1, 1, block_q, 1),
                             lambda bi, hi, qi, ti: (bi, hi, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k),
        grid=(b, h, s // block_q, s // tile),
        in_specs=[q_by_qi, kv_tile, kv_tile, q_by_qi, vec_by_qi,
                  vec_by_qi],
        out_specs=q_by_qi,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="hvt_flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: grid (b, h, ki, ti) — Q/dO/lse/delta tiles stream past
    # each K/V block (the reduction axis must be LAST). Under GQA the
    # kernel still reads the shared K/V head zero-copy but emits
    # per-QUERY-head gradients (full h), which are then group-summed —
    # each K/V head's gradient is the sum over its query group.
    # The dkv tile is capped independently of the fwd/dq tile: this
    # kernel streams Q AND dO tiles together and was the one that blew
    # scoped VMEM at tile 8192 (see _DKV_TILE_CAP).
    dkv_tile = _seq_tile(s, block_q, block_k, cap=_DKV_TILE_CAP)
    kv_in_ki = pl.BlockSpec((1, 1, block_k, d),
                            lambda bi, hi, ki, ti: (bi, hi // group, ki, 0))
    dkv_out_ki = pl.BlockSpec((1, 1, block_k, d),
                              lambda bi, hi, ki, ti: (bi, hi, ki, 0))
    q_tile = pl.BlockSpec((1, 1, dkv_tile, d),
                          lambda bi, hi, ki, ti: (bi, hi, ti, 0))
    vec_tile = pl.BlockSpec((1, 1, dkv_tile, 1),
                            lambda bi, hi, ki, ti: (bi, hi, ti, 0))
    full_shape = (b, h, s, d)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q),
        grid=(b, h, s // block_k, s // dkv_tile),
        in_specs=[kv_in_ki, kv_in_ki, q_tile, q_tile, vec_tile,
                  vec_tile],
        out_specs=[dkv_out_ki, dkv_out_ki],
        out_shape=[jax.ShapeDtypeStruct(full_shape, k.dtype),
                   jax.ShapeDtypeStruct(full_shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
        name="hvt_flash_dkv",
    )(k, v, q, do, lse, delta)
    if group > 1:
        h_kv = h // group
        dk = dk.astype(jnp.float32).reshape(
            b, h_kv, group, s, d).sum(axis=2).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(
            b, h_kv, group, s, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, scale=None,
                    block_q=128, block_k=128):
    """Fused multi-head attention.

    Args:
      q, k, v: [batch, seq, heads, head_dim] (BSHD, matching
        :mod:`horovod_tpu.models.transformer`).
      causal: apply causal masking.
      scale: softmax scale, default ``head_dim ** -0.5``.
      block_q / block_k: MXU tile sizes; clipped to divide seq.

    Returns [batch, seq, heads, head_dim] in q.dtype. Differentiable
    (custom VJP with recompute-based backward kernels).
    """
    o, _ = flash_attention_with_lse(q, k, v, causal=causal, scale=scale,
                                    block_q=block_q, block_k=block_k)
    return o


def flash_attention_with_lse(q, k, v, *, causal=True, scale=None,
                             block_q=128, block_k=128, out_dtype=None):
    """Fused attention returning ``(o, lse)``; both are differentiable.

    ``lse[b, s, h]`` is the log-sum-exp of the (scaled, masked) scores for
    each query — exactly what blockwise/ring composition needs to combine
    partial attention outputs: given per-block ``(o_i, lse_i)``, the total
    is ``o = Σ_i exp(lse_i − logaddexp_i lse_i) · o_i``
    (``parallel/sequence.py`` ring attention uses this).

    ``out_dtype`` (default ``q.dtype``): dtype o is written in. Blockwise
    consumers should pass ``jnp.float32`` so the fp32 accumulator reaches
    the combine unrounded; the matmuls still run on bf16 operands.
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(
            f"GQA requires n_heads ({h}) divisible by n_kv_heads "
            f"({h_kv})")
    if scale is None:
        scale = d ** -0.5
    block_q = _blocks(s, block_q)
    block_k = _blocks(s, block_k)
    if not _interpret() and (block_q % 8 or block_k % 8):
        # Mosaic refuses the kernel ("cannot statically prove that index
        # in dimension 2 is a multiple of 8"); the interpreter has no
        # such limit, so name it here rather than inside the compiler
        raise ValueError(
            f"flash attention compiles only with sequence blocks that "
            f"are multiples of 8: sequence length {s} clips the blocks "
            f"to ({block_q}, {block_k}). Pad the sequence to a multiple "
            f"of 8 or use the einsum path (use_flash=False)")
    # Kernels are gridded (batch, head, block): BHSD layout.
    to_bhsd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    o, lse = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v),
                    float(scale), bool(causal), block_q, block_k,
                    jnp.dtype(out_dtype or q.dtype))
    # lse: [B, H, S, 1] → [B, S, H]
    return jnp.transpose(o, (0, 2, 1, 3)), jnp.transpose(lse[..., 0],
                                                         (0, 2, 1))
