"""Gated DeltaNet mixer (Yang, Kautz and Hatamizadeh, arXiv:2412.06464; the
linear-attention layers of Qwen3-Next): a linear-cost layer whose state is
**read and corrected before it is written** (the delta rule), under a
data-dependent decay.

For an input ``u [batch, seq, d]``, ``H_k`` key heads of ``d_k`` channels
(``K = H_k d_k``), ``H_v`` value heads of ``d_v`` (``V = H_v d_v``; key
head ``j`` serves the ``H_v / H_k`` value heads ``[j r, (j + 1) r)``), the
layer is five steps, each under a ``jax.named_scope`` of its name so that a
device trace can be split by them:

1. ``gdn_in_proj``: ``[q | k | v | z] = u W_qkvz`` (widths ``K``, ``K``,
   ``V``, ``V``) and ``[b | a] = u W_ba`` (``H_v`` each); no bias.
2. ``gdn_conv``: ``[q | k | v] <- silu(conv([q | k | v]))``, the causal
   depthwise convolution of ``conv`` taps, no bias (``ssm.causal_conv``:
   on a TPU the Pallas kernels of ``ops/causal_conv.py``, which read and
   write the layer's ``dtype`` once and keep float32 in VMEM).
3. ``gdn_rule``: ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
   dt_bias)`` a value head (``g <= 0``), both float32; ``q`` and ``k``
   L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` then times
   ``d_k^-1/2`` (``l2_normalise``, on ``[b, s, H_k d_k]`` as the
   convolution left them, a head a group of ``d_k`` adjacent channels: on
   a TPU at heads that are multiples of 128 the Pallas kernels of
   ``ops/head_norm.py``, float32 in VMEM alone; everywhere else
   ``l2_normalise_plain``); a state ``S [d_k, d_v]`` a value head, from
   zero:

       S' = exp(g_t) S_{t-1}
       S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
       o_t = S_t^T q_t

   Computed **chunked** (``gated_delta_rule`` below): a chunk's
   corrections from a unit lower-triangular system, inverted by blocks,
   products inside the chunks, and a carry over them. The decays, their
   cumulative sums, the inverse and the carried state are float32
   (``STATE_DTYPE``), the products run in the layer's ``dtype`` and
   accumulate in float32. Two programs of the same equations, chosen from
   what can be observed (``kernels_serve``; no option): on a TPU, at the
   chunk of 128 and heads that are multiples of 128, the Pallas kernels of
   ``ops/gated_delta_rule.py`` (the state in VMEM, the chunks walked
   inside the kernels, a backward kernel of their own under a custom
   VJP); everywhere else ``gated_delta_rule_plain``, plain ``jax.numpy``
   whose backward pass is ``jax.grad`` of it, and the kernels' reference.
4. ``gdn_gate_norm``: ``RMSNorm(o) w * silu(z)`` a head: the norm over a
   head's ``d_v`` channels **before** the gate, ``w [d_v]`` shared by the
   heads (Mamba-2's ``ssm.gated_group_norm`` gates first: another
   function). ``gated_head_norm``, on ``[b, s, H_v d_v]``, which is what
   the rule's kernels write, ``z`` is and the out-projection reads:
   chosen as step 3's norms are, between ``ops/head_norm.py``'s kernels
   and ``gated_head_norm_plain``. Where the kernels serve, nothing
   between the convolution and the out-projection is reshaped to ``[b,
   s, H, d]`` (whose tiles differ from ``[b, s, H d]``'s at ``d`` = 128:
   a copy through HBM each) or written to HBM in float32.
5. ``gdn_out_proj``: ``y W_out``, no bias.

For a caller that asks for the collection ``intermediates`` the mixer's
own input and output are sown there (``gdn_input``, ``gdn_output``), for
a comparison with a position-by-position reference on the same input.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import ssm
from horovod_tpu.ops import gated_delta_rule as rule_kernels
from horovod_tpu.ops import head_norm as norm_kernels

# What the decays, their cumulative sums, the triangular inverse and the
# carried state are computed in, whatever the products run in. A module
# constant and no option: a test or a builder's experiment steers it from
# outside.
STATE_DTYPE = jnp.float32
# The chunk the rule takes where the caller names none: the longest the
# sequence allows up to this, and the one chunk the Pallas kernels serve
# (a chunk's [c, c] matrices are then whole 128 x 128 tiles). The inverse
# costs by the chunk's square a position and the carry by the number of
# chunks. On a v5e at 2 x 8192, 32 value heads of 128 x 128 on 16 key
# heads, bf16, a forward and backward of the plain path took 50.4 ms at 64
# (the source's kernel's), 49.8 at 128 and 87.1 at 256 (PERF.md section 6,
# PR 33); at 128 the states kept for the backward pass are half as many as
# at 64. The kernels at 128: chip_smoke.py's gdn8192 and PERF.md section
# 6, PR 34.
CHUNK = 128
# The precision of the float32 products that invert a chunk's system.
INVERSE_PRECISION = jax.lax.Precision.HIGHEST
# The source's initialisation: A uniform in (0, 16), kept away from 0,
# stored as its logarithm; dt_bias 1.
A_RANGE = (1e-3, 16.0)
L2_EPS = 1e-6


def _count_trace(value_heads, key_dim, value_dim, chunk):
    """The engagement counter: one count a traced layer. Trace-time
    Python only."""
    try:
        from horovod_tpu import metrics

        metrics.counter(
            "hvt_gdn_layers_traced_total",
            "Gated DeltaNet layers traced into compiled programs "
            "(counted per trace, not per execution)",
            ("value_heads", "key_dim", "value_dim", "chunk"),
        ).labels(value_heads=str(value_heads), key_dim=str(key_dim),
                 value_dim=str(value_dim), chunk=str(chunk)).inc()
    except Exception:
        pass  # telemetry must never break a trace


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the rule uses for ``seq_len`` positions."""
    return ssm.chunk_for(seq_len, chunk or CHUNK)


def _by_heads(x, dim):
    return x.reshape(*x.shape[:-1], x.shape[-1] // dim, dim)


def l2_normalise(x, dim: Optional[int] = None, scale: float = 1.0):
    """``x / sqrt(sum x^2 + 1e-6) * scale`` over each group of ``dim``
    adjacent channels of the last axis (a head of ``[..., H dim]``; the
    whole axis where none is named), float32 inside, like ``x``. By the
    Pallas kernels of ``ops/head_norm.py`` where ``norm_kernels.serves``
    says so and by ``l2_normalise_plain`` everywhere else."""
    dim = dim or x.shape[-1]
    if x.ndim == 3 and norm_kernels.serves(x.shape[1], dim):
        return norm_kernels.l2_norm(x, dim, eps=L2_EPS, scale=scale)
    return l2_normalise_plain(x, dim, scale)


def l2_normalise_plain(x, dim: Optional[int] = None, scale: float = 1.0):
    """``l2_normalise`` in plain ``jax.numpy``: the path of every backend
    and shape the kernels do not serve, and their reference."""
    heads = _by_heads(x.astype(jnp.float32), dim or x.shape[-1])
    return (heads * jax.lax.rsqrt(
        jnp.sum(heads * heads, axis=-1, keepdims=True) + L2_EPS) * scale
            ).astype(x.dtype).reshape(x.shape)


def _dot32(a, b):
    return jnp.matmul(a, b, precision=INVERSE_PRECISION)


@jax.custom_vjp
def unit_lower_inverse(lower):
    """``(I + N)^-1`` for ``N`` the strictly lower triangle of ``lower
    [..., c, c]`` (what is on or above the diagonal is not read), by
    blocks: the inverse of the diagonal blocks of size ``m`` is that of
    the blocks of size ``2 m`` once ``T <- T - T O T`` has been taken with
    ``O`` the part of ``N`` in the lower left quarter of each ``2 m``
    block (``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``).
    From ``m = 1``, where the inverse is the identity, ``ceil(log2 c)``
    such steps, two ``[c, c]`` products each, every intermediate the true
    inverse of a block-diagonal part of the system (no power of ``N`` is
    ever formed). In ``lower``'s dtype (the caller's ``STATE_DTYPE``) at
    ``INVERSE_PRECISION``. The backward pass is the inverse's own, ``-T^T
    g T^T``, so that it keeps ``T`` and no step's intermediate."""
    c = lower.shape[-1]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    n = jnp.tril(lower, -1)
    inverse = jnp.broadcast_to(jnp.eye(c, dtype=lower.dtype), n.shape)
    m = 1
    while m < c:
        quarter = ((row // (2 * m) == col // (2 * m))
                   & ((row // m) % 2 == 1) & ((col // m) % 2 == 0))
        inverse = inverse - _dot32(
            _dot32(inverse, jnp.where(quarter, n, 0.0)), inverse)
        m *= 2
    return inverse


def _unit_lower_inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (jnp.tril(-_dot32(_dot32(transposed, g), transposed), -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def kernels_serve(chunk: int, key_dim: int, value_dim: int) -> bool:
    """Whether the rule goes to the Pallas kernels of
    ``ops/gated_delta_rule.py``, from what can be observed (static
    trace-time facts, so the choice compiles away): a TPU backend
    (elsewhere the kernels are interpreted, far slower than
    ``jax.numpy``), the chunk they were measured at, and heads that fill
    whole 128-lane tiles. Everything else stays on
    ``gated_delta_rule_plain``, so the choice never raises for a shape
    that serves."""
    return (jax.default_backend() == "tpu" and chunk == CHUNK
            and key_dim % 128 == 0 and value_dim % 128 == 0)


def gated_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None):
    """The gated delta rule, chunked: ``gated_delta_rule_plain``'s
    arguments and result, by the Pallas kernels where ``kernels_serve``
    says so and by ``gated_delta_rule_plain`` itself everywhere else."""
    c = chunk_for(q.shape[1], chunk)
    if kernels_serve(c, q.shape[-1], v.shape[-1]):
        return rule_kernels.gated_delta_rule(
            q, k, v, g, beta, chunk=c, state_dtype=STATE_DTYPE,
            precision=INVERSE_PRECISION)
    return gated_delta_rule_plain(q, k, v, g, beta, chunk=chunk)


def gated_delta_rule_plain(q, k, v, g, beta, *,
                           chunk: Optional[int] = None):
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
    write nothing and are cut off again).
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
    f32 = jnp.float32
    # [b, n, key head, value head of it, position, ...]: a chunk's [c, c]
    # matrices with the positions last, so that they are the tile
    to_key = lambda t: jnp.transpose(
        t.reshape(batch, chunks, c, key_heads, d_k), (0, 1, 3, 2, 4))
    to_value = lambda t, *last: jnp.transpose(
        t.reshape(batch, chunks, c, key_heads, per_key, *last),
        (0, 1, 3, 4, 2) + tuple(range(5, 5 + len(last))))
    q, k = to_key(q), to_key(k)                     # [b, n, K, c, d_k]
    v = to_value(v, d_v)                            # [b, n, K, r, c, d_v]
    g = to_value(g.astype(STATE_DTYPE))             # [b, n, K, r, c]
    beta = to_value(beta.astype(STATE_DTYPE))

    cum = jnp.cumsum(g, axis=-1)                    # G_i, inclusive
    last = cum[..., -1]                             # [b, n, K, r]
    lag = cum[..., :, None] - cum[..., None, :]     # G_i - G_j
    at, before = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(at >= before, lag, -jnp.inf)).astype(f32)
    kk = jnp.einsum("bnkid,bnkjd->bnkij", k, k, preferred_element_type=f32)
    qk = jnp.einsum("bnkid,bnkjd->bnkij", q, k, preferred_element_type=f32)
    # T = (I + N)^-1, N_ij = beta_i (k_i . k_j) exp(G_i - G_j) for j < i
    inverse = unit_lower_inverse(
        (beta[..., :, None] * kk[:, :, :, None] * decay).astype(STATE_DTYPE)
    ).astype(dtype)
    by_key = lambda t: t[:, :, :, None]             # beside its value heads
    k_in = by_key(k).astype(f32) * (beta * jnp.exp(cum))[..., None]
    w = jnp.einsum("bnkrij,bnkrjd->bnkrid", inverse,
                   (v.astype(f32) * beta[..., None]).astype(dtype),
                   preferred_element_type=f32)
    u = jnp.einsum("bnkrij,bnkrjd->bnkrid", inverse, k_in.astype(dtype),
                   preferred_element_type=f32)
    k_out = (by_key(k).astype(f32)
             * jnp.exp(last[..., None] - cum)[..., None]).astype(dtype)

    def carry(state, chunk_in):
        w_c, u_c, k_c, keep = chunk_in      # a chunk's, [b, K, r, ...]
        new = (w_c - jnp.einsum("bkrid,bkrde->bkrie", u_c,
                                state.astype(dtype),
                                preferred_element_type=f32)).astype(dtype)
        added = jnp.einsum("bkrid,bkrie->bkrde", k_c, new,
                           preferred_element_type=f32)
        return ((state * keep[..., None, None] + added).astype(STATE_DTYPE),
                (new, state))

    first = lambda t: jnp.moveaxis(t, 1, 0)
    _, (new, entering) = jax.lax.scan(
        carry, jnp.zeros((batch, key_heads, per_key, d_k, d_v), STATE_DTYPE),
        (first(w), first(u.astype(dtype)), first(k_out),
         first(jnp.exp(last))))
    new, entering = jnp.moveaxis(new, 0, 1), jnp.moveaxis(entering, 0, 1)
    inside = (qk[:, :, :, None] * decay).astype(dtype)
    o = jnp.einsum("bnkrij,bnkrjd->bnkrid", inside, new,
                   preferred_element_type=f32)
    q_in = (by_key(q).astype(f32) * jnp.exp(cum)[..., None]).astype(dtype)
    o = o + jnp.einsum("bnkrid,bnkrde->bnkrie", q_in, entering.astype(dtype),
                       preferred_element_type=f32)
    o = jnp.transpose(o, (0, 1, 4, 2, 3, 5)).reshape(
        batch, seq + pad, value_heads, d_v)[:, :seq]
    return o.astype(dtype)


def gated_head_norm(o, z, scale, eps):
    """``RMSNorm(o) * scale * silu(z)`` with the mean square over each
    group of ``scale.shape[-1]`` adjacent channels of the last axis (a
    head of ``[..., H d]``, or of ``[..., H, d]``), the norm before the
    gate; float32 inside, like ``o``. By the Pallas kernels of
    ``ops/head_norm.py`` where ``norm_kernels.serves`` says so and by
    ``gated_head_norm_plain`` everywhere else."""
    if o.ndim == 3 and norm_kernels.serves(o.shape[1], scale.shape[-1]):
        return norm_kernels.gated_norm(o, z, scale, eps=eps)
    return gated_head_norm_plain(o, z, scale, eps)


def gated_head_norm_plain(o, z, scale, eps):
    """``gated_head_norm`` in plain ``jax.numpy``: the path of every
    backend and shape the kernels do not serve, and their reference."""
    o32 = _by_heads(o.astype(jnp.float32), scale.shape[-1])
    normed = o32 * jax.lax.rsqrt(
        jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    gate = jax.nn.silu(_by_heads(z.astype(jnp.float32), scale.shape[-1]))
    return (normed * scale * gate).astype(o.dtype).reshape(o.shape)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
                   ).astype(dtype)


class GatedDeltaNet(nn.Module):
    """The mixer. Parameters: ``in_proj_qkvz [d, 2 K + 2 V]`` (columns ``q
    | k | v | z``, each its heads in order), ``in_proj_ba [d, 2 H_v]``
    (``b | a``), ``conv_kernel [taps, 2 K + V]``, ``dt_bias``, ``A_log``
    ``[H_v]``, ``norm_scale [d_v]``, ``out_proj [V, d]``."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int = 4
    norm_eps: float = 1e-6
    chunk: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        d, seq = u.shape[-1], u.shape[-2]
        if self.value_heads % self.key_heads:
            raise ValueError(
                f"{self.value_heads} value heads over {self.key_heads} key "
                f"heads: a key head serves a whole number of value heads")
        keys = self.key_heads * self.key_dim
        values = self.value_heads * self.value_dim
        dense = nn.initializers.normal(0.02)
        w_qkvz = self.param("in_proj_qkvz", dense, (d, 2 * keys + 2 * values))
        w_ba = self.param("in_proj_ba", dense, (d, 2 * self.value_heads))
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (self.conv, 2 * keys + values))
        dt_bias = self.param("dt_bias", nn.initializers.ones_init(),
                             (self.value_heads,))
        a_log = self.param("A_log", _a_log_init, (self.value_heads,))
        norm_scale = self.param("norm_scale", nn.initializers.ones_init(),
                                (self.value_dim,))
        w_out = self.param("out_proj", dense, (values, d))
        _count_trace(self.value_heads, self.key_dim, self.value_dim,
                     chunk_for(seq, self.chunk))

        self.sow("intermediates", "gdn_input", u)
        lead = u.shape[:-2]
        u = u.reshape(-1, seq, d).astype(self.dtype)
        with jax.named_scope("gdn_in_proj"):
            qkv, z = jnp.split(jnp.dot(u, w_qkvz.astype(self.dtype)),
                               [2 * keys + values], -1)
            ba = jnp.dot(u, w_ba.astype(self.dtype),
                         preferred_element_type=STATE_DTYPE)
        with jax.named_scope("gdn_conv"):
            q, k, v = jnp.split(ssm.causal_conv(qkv, conv_kernel),
                                [keys, 2 * keys], -1)
        with jax.named_scope("gdn_rule"):
            heads = lambda t, n: t.reshape(*t.shape[:-1], n, -1)
            b, a = jnp.split(ba, 2, -1)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(a_log.astype(STATE_DTYPE)) * jax.nn.softplus(
                a + dt_bias.astype(STATE_DTYPE))
            # flat in, flat out; the rule takes heads and its kernels
            # flatten them again, a reshape and its inverse, which XLA
            # drops
            q = l2_normalise(q, self.key_dim, self.key_dim ** -0.5)
            k = l2_normalise(k, self.key_dim)
            o = gated_delta_rule(
                heads(q, self.key_heads), heads(k, self.key_heads),
                heads(v, self.value_heads), g, beta, chunk=self.chunk)
        with jax.named_scope("gdn_gate_norm"):
            y = gated_head_norm(o.reshape(*o.shape[:-2], values), z,
                                norm_scale, self.norm_eps)
        with jax.named_scope("gdn_out_proj"):
            out = jnp.dot(y, w_out.astype(self.dtype))
        out = out.reshape(*lead, seq, d)
        self.sow("intermediates", "gdn_output", out)
        return out


def gdn_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``GatedDeltaNet``: the value heads
    are the tensor-parallel dimension. The two in-projections' and the
    convolution's columns interleave ``q | k | v | z`` (``b | a``) and do
    not divide evenly over an axis, so they replicate, as
    ``ssm.ssm_leaf_spec`` has Mamba-2's; the per-head vectors and
    ``out_proj``'s rows shard; the norm's scale is one head's and
    replicates."""
    if name in ("dt_bias", "A_log"):
        return P(tp_axis)
    if name == "out_proj":
        return P(tp_axis, None)
    return P()
