"""Mamba-2 mixer (Dao and Gu, arXiv:2405.21060): a state-space layer
whose cost is linear in the sequence, for the heads it is given.

For an input ``u [batch, seq, d]``, ``heads`` heads of ``head_dim``
channels (inner width ``D = heads x head_dim``), ``G`` groups and a state
of ``N`` a channel, the layer is five steps, each under a
``jax.named_scope`` of its name so that a device trace can be split by
them:

1. ``ssm_in_proj``: ``[z | x | B | C | dt] = u W_in``, widths ``D``,
   ``D``, ``G N``, ``G N``, ``heads``; one product, no bias.
2. ``ssm_conv``: ``silu(conv(xBC) + b)``, a causal depthwise convolution
   of ``conv`` taps over the sequence, on ``x``, ``B`` and ``C`` together
   (``ops/causal_conv.py``, whose rule sends this mixer's own 1280
   channels in the cell ``nemotron3s-s8192`` to its plain body).
3. ``ssm_scan``: ``delta = softplus(dt + dt_bias)``, ``a = -exp(A_log)``
   a head, both float32; a state ``h [head_dim, N]`` a head,

       h_t = exp(delta_t a) h_{t-1} + delta_t x_t B_t^T
       y_t = h_t C_t + D_skip x_t

   (a head uses the ``B`` and ``C`` of its group). Computed **chunked**
   (``ssm_scan`` below): inside a chunk the products ``C B^T`` masked by
   the cumulative decay, between chunks a carried state. The decays and
   their cumulative sums are float32 (``DECAY_DTYPE``), the products run
   in the layer's ``dtype`` and accumulate in float32. The backward pass
   is ``jax.grad`` of that.
4. ``ssm_gate_norm``: ``RMSNorm_by_group(y * silu(z)) * w``, the norm
   taken over each group's ``D / G`` channels, float32.
5. ``ssm_out_proj``: ``y W_out``, no bias.

**A chip's share.** ``held = (first, count)`` builds the layer over heads
``[first, first + count)`` of ``heads`` and the groups they use: the
slices of ``W_in``, the convolution, ``dt_bias``, ``A_log``, ``D_skip``,
the norm's scale and ``W_out`` that tensor parallelism over the heads
gives one chip. Whole groups only, so the grouped norm stays local. The
output is then that share of the sum over all heads; nothing stands in
for the other chips' shares or for the exchange that would add them.

For a caller that asks for the collection ``intermediates`` the mixer's
own input and output are sown there (``ssm_input``, ``ssm_output``), for
a comparison with a position-by-position reference on the same input.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import causal_conv as conv_op

# What the decays, their cumulative sums and the carried state are
# computed in, whatever the products run in. A module constant and no
# option: a test or a builder's experiment steers it from outside.
DECAY_DTYPE = jnp.float32
# The chunk the scan takes where the caller names none: the longest the
# sequence allows up to this. At 256 a chunk's masked [chunk, chunk]
# decays are 256 KiB a head in float32, its products fill the MXU's
# contraction, and 8192 positions are 32 steps of the carry.
CHUNK = 256
# Mamba-2's own initialisation of the step size: dt log-uniform in
# [DT_MIN, DT_MAX], floored at DT_FLOOR, stored as its inverse softplus;
# A uniform in [1, 16], stored as its logarithm.
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)


def _count_trace(heads, state, chunk):
    """One count a traced layer."""
    _pallas.count_trace(
        "hvt_ssm_layers_traced_total",
        "state-space (Mamba-2) layers traced into compiled programs "
        "(counted per trace, not per execution)",
        heads=heads, state=state, chunk=chunk)


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the scan uses for ``seq_len`` positions."""
    return max(1, min(chunk or CHUNK, seq_len))


def ssm_scan(x, delta, a, b, c, *, chunk: Optional[int] = None):
    """The selective scan, chunked.

    ``x [batch, s, heads, p]``, ``delta [batch, s, heads]`` float32 (after
    its softplus), ``a [heads]`` float32 (negative), ``b`` and ``c``
    ``[batch, s, groups, n]``; a head uses group ``head // (heads /
    groups)``. Returns ``y [batch, s, heads, p]`` in ``x.dtype`` with
    ``y_t = C_t h_t`` and ``h_t = exp(delta_t a) h_{t-1} + delta_t x_t
    B_t^T`` from ``h = 0`` (the skip term is the caller's).

    With ``l_t`` the cumulative sum of ``delta a`` inside a chunk, a
    position reads its own chunk's earlier positions through
    ``exp(l_t - l_s) (C_t . B_s)`` for ``s <= t`` (one masked
    ``[chunk, chunk]`` matrix a head) and everything before the chunk
    through the state carried into it, decayed by ``exp(l_t)``; a chunk
    hands on ``exp(l_last) h_in + sum_s exp(l_last - l_s) delta_s x_s
    B_s^T``. The carry is a ``lax.scan`` over the chunks. A sequence the
    chunk does not divide is padded with positions whose ``delta`` is 0
    (they decay nothing, add nothing and are cut off again).
    """
    batch, seq, heads, p = x.shape
    groups, n = b.shape[-2:]
    per_group = heads // groups
    q = chunk_for(seq, chunk)
    pad = -seq % q
    if pad:
        grow = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        x, delta, b, c = grow(x), grow(delta), grow(b), grow(c)
    chunks = (seq + pad) // q
    dtype = x.dtype
    # a head beside its group; the decays [b, chunks, g, h, q] with the
    # positions last, so that a chunk's [q, q] matrix is the tile
    x = x.reshape(batch, chunks, q, groups, per_group, p)
    b = b.reshape(batch, chunks, q, groups, n)
    c = c.reshape(batch, chunks, q, groups, n)
    delta = jnp.transpose(
        delta.astype(DECAY_DTYPE).reshape(batch, chunks, q, groups,
                                          per_group), (0, 1, 3, 4, 2))
    log_decay = delta * a.astype(DECAY_DTYPE).reshape(groups, per_group, 1)
    cum = jnp.cumsum(log_decay, axis=-1)                # l_t, inclusive
    last = cum[..., -1]                                 # [b, c, g, h]
    by_position = lambda t: jnp.transpose(t, (0, 1, 4, 2, 3))[..., None]
    x_delta = (x.astype(jnp.float32)
               * by_position(delta).astype(jnp.float32)).astype(dtype)

    # inside the chunks: (C_t . B_s) exp(l_t - l_s) for s <= t
    scores = jnp.einsum("bctgn,bcsgn->bcgts", c, b,
                        preferred_element_type=jnp.float32)
    lag = cum[..., :, None] - cum[..., None, :]         # [b, c, g, h, t, s]
    mask = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(mask, lag, -jnp.inf)).astype(jnp.float32)
    weights = (scores[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcghts,bcsghp->bctghp", weights, x_delta,
                   preferred_element_type=jnp.float32)

    # what a chunk adds to the state it hands on
    to_end = jnp.exp(last[..., None] - cum).astype(jnp.float32)
    added = jnp.einsum(
        "bcsgn,bcsghp->bcghpn", b,
        (x_delta.astype(jnp.float32) * by_position(to_end)).astype(dtype),
        preferred_element_type=jnp.float32)

    def carry(state, chunk_in):
        keep, add = chunk_in                # [b, g, h], [b, g, h, p, n]
        return (state * keep[..., None, None] + add).astype(DECAY_DTYPE), state

    init = jnp.zeros((batch, groups, per_group, p, n), DECAY_DTYPE)
    _, entering = jax.lax.scan(
        carry, init, (jnp.moveaxis(jnp.exp(last), 1, 0),
                      jnp.moveaxis(added.astype(DECAY_DTYPE), 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # [b, c, g, h, p, n]
    from_before = jnp.einsum("bctgn,bcghpn->bctghp", c,
                             entering.astype(dtype),
                             preferred_element_type=jnp.float32)
    y = y + from_before * by_position(jnp.exp(cum).astype(jnp.float32))
    y = y.reshape(batch, seq + pad, heads, p)[:, :seq]
    return y.astype(dtype)


def gated_group_norm(y, z, scale, groups, eps):
    """``RMSNorm(y * silu(z)) * scale`` with the mean square taken over
    each of ``groups`` equal runs of the last axis; float32 inside."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(*gated.shape[:-1], groups, -1)
    var = jnp.mean(by_group * by_group, axis=-1, keepdims=True)
    normed = (by_group * jax.lax.rsqrt(var + eps)).reshape(gated.shape)
    return (normed * scale).astype(y.dtype)


def held_groups(heads, groups, held):
    """``(heads, groups)`` of a share ``held = (first, count)`` of
    ``heads`` heads in ``groups`` groups (``None``: all of them). A share
    is whole groups."""
    first, count = held or (0, heads)
    per_group = heads // groups
    if (heads % groups or first % per_group or count % per_group
            or not 0 < count <= heads - first):
        raise ValueError(
            f"heads held {held} of {heads} heads in {groups} groups: a "
            f"share is whole groups of {per_group} heads")
    return count, count // per_group


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)    # softplus^-1


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
                   ).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The mixer over the heads ``held`` (default: all ``heads``).
    Parameters, each the share's slice: ``in_proj [d, 2 D + 2 G N +
    heads]`` (columns ``z | x | B | C | dt``), ``conv_kernel [taps, D +
    2 G N]`` and ``conv_bias``, ``dt_bias``, ``A_log``, ``D_skip``
    ``[heads]``, ``norm_scale [D]``, ``out_proj [D, d]``."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int = 4
    held: Optional[tuple] = None
    norm_eps: float = 1e-5
    chunk: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        d, seq = u.shape[-1], u.shape[-2]
        heads, groups = held_groups(self.heads, self.groups, self.held)
        inner, bc = heads * self.head_dim, groups * self.state
        dense = nn.initializers.lecun_normal()
        w_in = self.param("in_proj", dense, (d, 2 * inner + 2 * bc + heads))
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (self.conv, inner + 2 * bc))
        conv_bias = self.param("conv_bias", nn.initializers.zeros_init(),
                               (inner + 2 * bc,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        d_skip = self.param("D_skip", nn.initializers.ones_init(), (heads,))
        norm_scale = self.param("norm_scale", nn.initializers.ones_init(),
                                (inner,))
        w_out = self.param("out_proj", dense, (inner, d))
        _count_trace(heads, self.state, chunk_for(seq, self.chunk))

        self.sow("intermediates", "ssm_input", u)
        lead = u.shape[:-2]
        u = u.reshape(-1, seq, d).astype(self.dtype)
        with jax.named_scope("ssm_in_proj"):
            zxbcdt = jnp.dot(u, w_in.astype(self.dtype))
            z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], -1)
        with jax.named_scope("ssm_conv"):
            xbc = conv_op.causal_conv(xbc, conv_kernel, conv_bias)
            x, b, c = jnp.split(xbc, [inner, inner + bc], -1)
        with jax.named_scope("ssm_scan"):
            x = x.reshape(*x.shape[:-1], heads, self.head_dim)
            b, c = (t.reshape(*t.shape[:-1], groups, self.state)
                    for t in (b, c))
            delta = jax.nn.softplus(dt.astype(DECAY_DTYPE)
                                    + dt_bias.astype(DECAY_DTYPE))
            a = -jnp.exp(a_log.astype(DECAY_DTYPE))
            y = ssm_scan(x, delta, a, b, c, chunk=self.chunk)
            y = (y.astype(jnp.float32) + x.astype(jnp.float32)
                 * d_skip.astype(jnp.float32)[:, None]).astype(self.dtype)
        with jax.named_scope("ssm_gate_norm"):
            y = gated_group_norm(y.reshape(*y.shape[:-2], inner), z,
                                 norm_scale, groups, self.norm_eps)
        with jax.named_scope("ssm_out_proj"):
            out = jnp.dot(y, w_out.astype(self.dtype))
        out = out.reshape(*lead, seq, d)
        self.sow("intermediates", "ssm_output", out)
        return out


def ssm_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``Mamba2Mixer``: the heads are the
    tensor-parallel dimension. ``in_proj``'s and the convolution's
    columns interleave ``z | x | B | C | dt`` and do not divide evenly
    over an axis, so they replicate here (a chip's share of them is
    built with ``held``); the per-head vectors and ``out_proj``'s rows
    shard."""
    if name in ("dt_bias", "A_log", "D_skip", "norm_scale"):
        return P(tp_axis)
    if name == "out_proj":
        return P(tp_axis, None)
    return P()
