"""Sequence / context parallelism — ring attention and Ulysses.

The reference has no sequence parallelism (SURVEY.md §5.7: the only relevant
primitive is ``alltoall``, reference ``operations.cc:1099``). On TPU long
context is first-class, so this module provides the two standard strategies,
built on XLA collectives over ICI:

- **Ring attention** (`ring_attention`): each device owns a sequence shard of
  Q and streams K/V shards around the ring with ``lax.ppermute`` while
  accumulating flash-attention-style online softmax. Peak memory per device is
  O(seq/N); comm is overlap-friendly neighbor exchange on the ICI torus.
  (Pattern: Liu et al., "Ring Attention with Blockwise Transformers", 2023.)

- **Ulysses attention** (`ulysses_attention`): ``lax.all_to_all`` reshards
  from sequence-sharded to head-sharded, runs dense local attention over the
  full sequence, and reshards back. Comm volume is O(seq·d) per device pair
  but only 2 all-to-alls per layer; best when heads ≥ devices.
  (Pattern: DeepSpeed-Ulysses, Jacobs et al., 2023.)

Both are written as **per-shard functions** to be used under
``jax.shard_map`` (or inside a larger shard_mapped training step), plus
convenience wrappers that apply shard_map for you.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _local_attention(q, k, v, q_pos, k_pos, *, causal, scale):
    """One blockwise attention step, returning unnormalized (o, m, l).

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; q_pos/k_pos: global token indices
    used for causal masking across sequence shards.
    Returns o [B, Sq, H, D] (fp32), m, l [B, H, Sq] (fp32 running max / sum).
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None]
        scores = jnp.where(mask, scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)                      # [B, H, Sq]
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)                           # [B, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def ring_attention_shard(q, k, v, *, axis_name, causal=True, scale=None,
                         use_flash=False):
    """Ring attention on per-device shards; call under ``shard_map``.

    Args:
      q, k, v: [batch, seq_shard, heads, head_dim] — this device's sequence
        shard (sequence axis sharded over ``axis_name``).
      axis_name: mesh axis carrying the sequence shards.
      causal: apply a causal mask using *global* token positions.
      scale: softmax scale; default ``head_dim ** -0.5``.
      use_flash: run each K/V block through the pallas fused kernel
        (``ops/flash_attention.py``) instead of the einsum-softmax block
        step — O(shard) VMEM-resident scores instead of a materialized
        [Sq × Sk] tile. Blocks combine via the kernel's differentiable
        logsumexp output. ``"auto"`` resolves by THIS function's local
        shard length (``q.shape[1]``) against the measured crossover —
        resolved here, under shard_map, where the per-device shape is
        unambiguous regardless of who owns the shard_map (ADVICE r4).

    Returns [batch, seq_shard, heads, head_dim] in q.dtype.
    """
    from horovod_tpu.ops.flash_attention import resolve_flash

    if resolve_flash(use_flash, q.shape[1]):
        return _ring_flash_shard(q, k, v, axis_name=axis_name,
                                 causal=causal, scale=scale)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    # Grouped-query attention: K/V may carry FEWER heads than Q. The
    # ring circulates the small K/V buffers (ICI payload shrinks by the
    # group factor — the point of GQA at long context) and each step
    # broadcasts them to the query head count LOCALLY, where XLA fuses
    # the repeat into the attention einsum instead of materializing it.
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"query heads ({h}) must be a multiple of K/V "
                         f"heads ({h_kv})")
    group = h // h_kv
    if scale is None:
        scale = d ** -0.5

    q_pos = idx * s + jnp.arange(s)
    perm = [(j, (j + 1) % n) for j in range(n)]

    o0 = jnp.zeros((b, s, h, d), jnp.float32)
    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)

    def body(step, carry):
        o, m, l, k_blk, v_blk = carry
        # After `step` rotations this device holds the shard that started on
        # ring neighbor (idx - step) mod n.
        k_idx = (idx - step) % n
        k_pos = k_idx * s + jnp.arange(s)
        if group > 1:   # local GQA broadcast; the RING carries h_kv heads
            kb = jnp.repeat(k_blk, group, axis=2)
            vb = jnp.repeat(v_blk, group, axis=2)
        else:
            kb, vb = k_blk, v_blk
        o_blk, m_blk, l_blk = _local_attention(
            q, kb, vb, q_pos, k_pos, causal=causal, scale=scale)
        m_new = jnp.maximum(m, m_blk)
        c_old = jnp.exp(m - m_new)        # rescale previous accumulator
        c_blk = jnp.exp(m_blk - m_new)
        l_new = l * c_old + l_blk * c_blk
        o_new = (o * c_old.transpose(0, 2, 1)[..., None]
                 + o_blk * c_blk.transpose(0, 2, 1)[..., None])
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    # Fully-masked rows (can't happen with causal self-attention over the
    # full ring, but guard against l == 0 from user masks).
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _ring_flash_shard(q, k, v, *, axis_name, causal, scale):
    """Ring attention where each block step IS the flash kernel.

    With sequence shards, the causal structure is block-triangular: the
    K/V shard that started on this device attends causally (the kernel's
    own mask — positions align), shards from EARLIER ring positions are
    fully visible (no mask), and later shards are fully hidden (skipped
    via an lse of −∞, so their combine weight underflows to exactly 0).
    Blocks merge by the flash kernel's differentiable logsumexp:
    ``o = Σ_i exp(lse_i − logaddexp_i lse_i) · o_i``.
    """
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    perm = [(j, (j + 1) % n) for j in range(n)]

    def flash_blk(blk_causal):
        def run(k_blk, v_blk):
            # out_dtype fp32: the kernel's accumulator reaches the
            # logsumexp combine unrounded (parity with the einsum ring
            # path, which carries fp32 end-to-end)
            o, lse = flash_attention_with_lse(q, k_blk, v_blk,
                                              causal=blk_causal,
                                              scale=scale,
                                              out_dtype=jnp.float32)
            return o, lse
        return run

    def masked_blk(k_blk, v_blk):
        return (jnp.zeros((b, s, h, d), jnp.float32),
                jnp.full((b, s, h), _NEG_INF, jnp.float32))

    def body(step, carry):
        o, lse, k_blk, v_blk = carry
        k_idx = (idx - step) % n
        if causal:
            case = jnp.where(k_idx == idx, 0,
                             jnp.where(k_idx < idx, 1, 2))
            o_blk, lse_blk = lax.switch(
                case, [flash_blk(True), flash_blk(False), masked_blk],
                k_blk, v_blk)
        else:
            o_blk, lse_blk = flash_blk(False)(k_blk, v_blk)
        lse_new = jnp.logaddexp(lse, lse_blk)
        w_old = jnp.exp(lse - lse_new)[..., None]
        w_blk = jnp.exp(lse_blk - lse_new)[..., None]
        o_new = o * w_old + o_blk * w_blk
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return o_new, lse_new, k_nxt, v_nxt

    o0 = jnp.zeros((b, s, h, d), jnp.float32)
    # finite −∞ stand-in: fully-masked rows produce 0, never inf−inf NaN
    lse0 = jnp.full((b, s, h), _NEG_INF, jnp.float32)
    o, _, _, _ = lax.fori_loop(0, n, body, (o0, lse0, k, v))
    return o.astype(q.dtype)


def ulysses_attention_shard(q, k, v, *, axis_name, causal=True, scale=None,
                            attn_fn=None, use_flash=False):
    """Ulysses (all-to-all) attention on per-device shards; under shard_map.

    Reshard [B, S/N, H, D] → all_to_all → [B, S, H/N, D], run dense local
    attention over the full sequence with a head subset, reshard back.
    ``heads`` must be divisible by the axis size.

    After the head exchange the local problem IS full-sequence causal
    attention, so ``use_flash=True`` runs it through the pallas fused
    kernel (``ops/flash_attention.py``) — O(seq) memory where the dense
    path materializes the [S × S] score matrix. ``attn_fn`` overrides
    both.
    """
    n = lax.axis_size(axis_name)
    b, s, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"Ulysses needs heads ({h}) divisible by the "
                         f"sequence-parallel axis size ({n})")
    # GQA: K/V may carry fewer heads; the head-exchange all_to_all then
    # needs the K/V head count divisible by the axis too (each device
    # ends up with h/n query heads and h_kv/n K/V heads — the group
    # structure is preserved because consecutive query heads share a
    # K/V head)
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"query heads ({h}) must be a multiple of K/V "
                         f"heads ({h_kv})")
    if h_kv != h and h_kv % n != 0:
        raise ValueError(
            f"Ulysses with grouped-query K/V needs K/V heads ({h_kv}) "
            f"divisible by the axis size ({n}); repeat K/V to the "
            f"query head count first for smaller head counts")
    group = h // h_kv
    if scale is None:
        scale = d ** -0.5

    def a2a(x, fwd):
        # tiled all_to_all: split heads across devices, gather sequence
        # (fwd) or the reverse.
        split, concat = (2, 1) if fwd else (1, 2)
        return lax.all_to_all(x, axis_name, split_axis=split,
                              concat_axis=concat, tiled=True)

    qg, kg, vg = a2a(q, True), a2a(k, True), a2a(v, True)  # [B, S, H/N, D]
    # after the head exchange the local problem is FULL-sequence
    # attention, so "auto" resolves against the gathered length
    from horovod_tpu.ops.flash_attention import resolve_flash

    if attn_fn is None and resolve_flash(use_flash, qg.shape[1]):
        from horovod_tpu.ops.flash_attention import flash_attention

        # the kernel serves GQA zero-copy (head-index aliasing)
        attn_fn = functools.partial(flash_attention, causal=causal,
                                    scale=scale)
    if attn_fn is None:
        if group > 1:   # local broadcast for the dense einsum path
            kg = jnp.repeat(kg, group, axis=2)
            vg = jnp.repeat(vg, group, axis=2)
        pos = jnp.arange(s * n)
        og, _, l = _local_attention(qg, kg, vg, pos, pos,
                                    causal=causal, scale=scale)
        og = (og / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
              ).astype(q.dtype)
    else:
        og = attn_fn(qg, kg, vg)
    return a2a(og, False)


def _wrap(shard_fn, q, k, v, *, mesh, axis_name, seq_specs, **kw):
    fn = functools.partial(shard_fn, axis_name=axis_name, **kw)
    return jax.shard_map(fn, mesh=mesh, in_specs=(seq_specs,) * 3,
                      out_specs=seq_specs, check_vma=False)(q, k, v)


def ring_attention(q, k, v, *, mesh, axis_name="sp", seq_specs=None,
                   causal=True, scale=None, use_flash=False):
    """Global-array convenience wrapper: shard_map + `ring_attention_shard`.

    ``seq_specs`` is the PartitionSpec of q/k/v (default: batch over 'dp' if
    present, sequence over ``axis_name``, heads over 'tp' if present).
    """
    if seq_specs is None:
        seq_specs = _default_specs(mesh, axis_name)
    return _wrap(ring_attention_shard, q, k, v, mesh=mesh,
                 axis_name=axis_name, seq_specs=seq_specs,
                 causal=causal, scale=scale, use_flash=use_flash)


def ulysses_attention(q, k, v, *, mesh, axis_name="sp", seq_specs=None,
                      causal=True, scale=None, use_flash=False):
    """Global-array convenience wrapper for `ulysses_attention_shard`."""
    if seq_specs is None:
        seq_specs = _default_specs(mesh, axis_name)
    return _wrap(ulysses_attention_shard, q, k, v, mesh=mesh,
                 axis_name=axis_name, seq_specs=seq_specs,
                 causal=causal, scale=scale, use_flash=use_flash)


def _default_specs(mesh, axis_name):
    names = mesh.axis_names
    dp = "dp" if "dp" in names else None
    tp = "tp" if "tp" in names else None
    return P(dp, axis_name, tp, None)
