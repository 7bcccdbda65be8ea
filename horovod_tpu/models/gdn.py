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
   depthwise convolution of ``conv`` taps, no bias
   (``ops/causal_conv.py``).
3. ``gdn_rule``: ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
   dt_bias)`` a value head (``g <= 0``), both float32; ``q`` and ``k``
   L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` then times
   ``d_k^-1/2`` (``ops/head_norm.py``'s ``l2_norm``, on ``[b, s, H_k
   d_k]`` as the convolution left them, a head a group of ``d_k`` adjacent
   channels); a state ``S [d_k, d_v]`` a value head, from zero:

       S' = exp(g_t) S_{t-1}
       S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
       o_t = S_t^T q_t

   Computed **chunked** (``ops/gated_delta_rule.py``): a chunk's
   corrections from a unit lower-triangular system, inverted by blocks,
   products inside the chunks, and a carry over them. The decays, their
   cumulative sums, the inverse and the carried state are float32
   (``STATE_DTYPE``), the products run in the layer's ``dtype`` and
   accumulate in float32.
4. ``gdn_gate_norm``: ``RMSNorm(o) w * silu(z)`` a head: the norm over a
   head's ``d_v`` channels **before** the gate, ``w [d_v]`` shared by the
   heads (Mamba-2's ``ssm.gated_group_norm`` gates first: another
   function). ``ops/head_norm.py``'s ``gated_norm``, on ``[b, s, H_v
   d_v]``, which is what the rule's kernels write, ``z`` is and the
   out-projection reads.
5. ``gdn_out_proj``: ``y W_out``, no bias.

Each of the three ops is a module under ``ops/`` that holds a plain
``jax.numpy`` body, Pallas kernels and the rule (``serves``) that chooses
between them from the backend and the shape; no option. Where the kernels
serve, nothing between the convolution and the out-projection is reshaped
to ``[b, s, H, d]`` (whose tiles differ from ``[b, s, H d]``'s at ``d`` =
128: a copy through HBM each) or written to HBM in float32.

For a caller that asks for the collection ``intermediates`` the mixer's
own input and output are sown there (``gdn_input``, ``gdn_output``), for
a comparison with a position-by-position reference on the same input.

The sibling with **a decay a key channel** is ``models/kda.py`` (Kimi
Delta Attention) over ``ops/channel_delta_rule.py``. It shares this
mixer's convolution, L2 norms, ``beta``, norm-then-gate and
out-projection; it differs in the gate's shape (``g [b, s, H, d_k]`` from
a low-rank pair of projections, with ``dt_bias`` a channel), in key and
value heads being equal in number, in the output gate's activation
(sigmoid, from a low-rank pair too) and in the rule it calls: there the
decay sits inside the sum over channels and is folded into the products'
operands, here it is a ``[c, c]`` mask after them. Both rules keep one
contract, stated in the same words in both ``ops/`` modules.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import causal_conv as conv_op
from horovod_tpu.ops import gated_delta_rule as rule_op
from horovod_tpu.ops import head_norm as norm_op

# What the decays, their cumulative sums, the triangular inverse and the
# carried state are computed in, whatever the products run in. A module
# constant and no option: a test or a builder's experiment steers it from
# outside.
STATE_DTYPE = jnp.float32
# The chunk the rule takes where the caller names none: the one its
# kernels were measured at.
CHUNK = rule_op.CHUNK
# The precision of the float32 products that invert a chunk's system.
INVERSE_PRECISION = jax.lax.Precision.HIGHEST
# The source's initialisation: A uniform in (0, 16), kept away from 0,
# stored as its logarithm; dt_bias 1.
A_RANGE = (1e-3, 16.0)
L2_EPS = 1e-6


def _count_trace(value_heads, key_dim, value_dim, chunk):
    """One count a traced layer."""
    _pallas.count_trace(
        "hvt_gdn_layers_traced_total",
        "Gated DeltaNet layers traced into compiled programs "
        "(counted per trace, not per execution)",
        value_heads=value_heads, key_dim=key_dim, value_dim=value_dim,
        chunk=chunk)


def chunk_for(seq_len: int, chunk: Optional[int] = None) -> int:
    """The chunk length the rule uses for ``seq_len`` positions."""
    return rule_op.chunk_for(seq_len, chunk or CHUNK)


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
        chunk = chunk_for(seq, self.chunk)
        _count_trace(self.value_heads, self.key_dim, self.value_dim, chunk)

        self.sow("intermediates", "gdn_input", u)
        lead = u.shape[:-2]
        u = u.reshape(-1, seq, d).astype(self.dtype)
        with jax.named_scope("gdn_in_proj"):
            qkv, z = jnp.split(jnp.dot(u, w_qkvz.astype(self.dtype)),
                               [2 * keys + values], -1)
            ba = jnp.dot(u, w_ba.astype(self.dtype),
                         preferred_element_type=STATE_DTYPE)
        with jax.named_scope("gdn_conv"):
            q, k, v = jnp.split(conv_op.causal_conv(qkv, conv_kernel),
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
            q = norm_op.l2_norm(q, self.key_dim, eps=L2_EPS,
                                scale=self.key_dim ** -0.5)
            k = norm_op.l2_norm(k, self.key_dim, eps=L2_EPS)
            o = rule_op.gated_delta_rule(
                heads(q, self.key_heads), heads(k, self.key_heads),
                heads(v, self.value_heads), g, beta, chunk=chunk,
                state_dtype=STATE_DTYPE, precision=INVERSE_PRECISION)
        with jax.named_scope("gdn_gate_norm"):
            y = norm_op.gated_norm(o.reshape(*o.shape[:-2], values), z,
                                   norm_scale, eps=self.norm_eps)
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
