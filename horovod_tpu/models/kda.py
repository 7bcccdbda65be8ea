"""Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692): the Gated
DeltaNet of ``models/gdn.py`` with **a decay a key channel** in place of
one a head, low-rank projections for the decay and the output gate, and a
sigmoid for that gate.

For an input ``u [batch, seq, d]``, ``H`` heads of ``d_h`` key channels and
as many value channels (``P = H d_h``), the layer is five steps, each under
a ``jax.named_scope`` of its name so that a device trace can be split by
them:

1. ``kda_in_proj``: six products, no bias: ``[q | k | v] = u W_qkv`` (the
   source's three projections laid side by side, ``P`` each), ``b = u
   W_beta`` (``H``), the decay's pair ``(u W_fa) W_fb`` (``d -> rank ->
   P``) and the gate's pair ``z = (u W_ga) W_gb``.
2. ``kda_conv``: ``[q | k | v] <- silu(conv([q | k | v]))``, the causal
   depthwise convolution of ``conv`` taps, no bias
   (``ops/causal_conv.py``; the source's three convolutions side by side).
3. ``kda_rule``: ``beta = sigmoid(b)`` a head, ``g = -exp(A_log)
   softplus((u W_fa) W_fb + dt_bias)`` **a key channel** (``A_log [H]``
   over a head's channels, ``dt_bias [P]``; ``g <= 0``), both float32;
   ``q`` and ``k`` L2-normalised a head, ``q`` then times ``d_h^-1/2``
   (``ops/head_norm.py``'s ``l2_norm``); a state ``S [d_h, d_h]`` a head,
   from zero:

       S' = Diag(exp(g_t)) S_{t-1}
       S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
       o_t = S_t^T q_t

   Computed **chunked** (``ops/channel_delta_rule.py``: on a TPU, at
   heads in whole 128-lane tiles, its three Pallas kernels, which read
   ``q``, ``k``, ``v`` and ``g`` as the ``[b, s, H d]`` arrays the
   convolution and the norms hand over; its plain ``jax.numpy`` body
   everywhere else). With ``g_t`` the same in every channel it is
   ``models/gdn.py``'s rule; what differs is that the decay sits inside
   the sum over channels, so it is folded into the products' operands
   through reference rows and not applied as a ``[c, c]`` mask after
   them. The decays, their cumulative sums, the inverse and the carried
   state are float32 (``STATE_DTYPE``), the products run in the layer's
   ``dtype`` and accumulate in float32.
4. ``kda_gate_norm``: ``RMSNorm(o) w * sigmoid(z)`` a head, the norm
   before the gate, ``w [d_h]`` shared by the heads (``ops/head_norm.py``'s
   ``gated_norm`` with ``gate="sigmoid"``; Gated DeltaNet's is ``silu``).
5. ``kda_out_proj``: ``y W_out``, no bias.

For a caller that asks for the collection ``intermediates`` the mixer's
own input and output are sown there (``kda_input``, ``kda_output``), for
a comparison with a position-by-position reference on the same input.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import causal_conv as conv_op
from horovod_tpu.ops import channel_delta_rule as rule_op
from horovod_tpu.ops import head_norm as norm_op

# What the decays, their cumulative sums, the triangular inverse and the
# carried state are computed in, whatever the products run in. A module
# constant and no option: a test or a builder's experiment steers it from
# outside.
STATE_DTYPE = jnp.float32
# The precision of the float32 products that invert a chunk's system.
INVERSE_PRECISION = jax.lax.Precision.HIGHEST
# The initialisation of the source's own layer (flash-linear-attention's
# ``KimiDeltaAttention``): A uniform in (1, 16) stored as its logarithm;
# dt_bias the softplus-inverse of a step drawn log-uniform in (1e-3, 0.1),
# as the state-space family draws its own.
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 0.1)
L2_EPS = 1e-6


def _count_trace(heads, head_dim, rank, chunk):
    """One count a traced layer."""
    _pallas.count_trace(
        "hvt_kda_layers_traced_total",
        "Kimi Delta Attention layers traced into compiled programs "
        "(counted per trace, not per execution)",
        heads=heads, head_dim=head_dim, gate_rank=rank, chunk=chunk)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    low, high = (jnp.log(x) for x in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)   # softplus^-1


class KimiDeltaAttention(nn.Module):
    """The mixer. Parameters: ``in_proj_qkv [d, 3 P]`` (columns ``q | k |
    v``, each its heads in order), ``conv_kernel [taps, 3 P]``,
    ``in_proj_beta [d, H]``, ``decay_down [d, rank]``, ``decay_up [rank,
    P]``, ``dt_bias [P]``, ``A_log [H]``, ``gate_down [d, rank]``,
    ``gate_up [rank, P]``, ``norm_scale [d_h]``, ``out_proj [P, d]``."""

    heads: int
    head_dim: int = 128
    conv: int = 4
    gate_rank: int = 128
    norm_eps: float = 1e-6
    chunk: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        d, seq = u.shape[-1], u.shape[-2]
        h, d_h, rank = self.heads, self.head_dim, self.gate_rank
        width = h * d_h
        dense = nn.initializers.normal(0.02)
        w_qkv = self.param("in_proj_qkv", dense, (d, 3 * width))
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (self.conv, 3 * width))
        w_beta = self.param("in_proj_beta", dense, (d, h))
        w_fa = self.param("decay_down", dense, (d, rank))
        w_fb = self.param("decay_up", dense, (rank, width))
        dt_bias = self.param("dt_bias", _dt_bias_init, (width,))
        a_log = self.param("A_log", _a_log_init, (h,))
        w_ga = self.param("gate_down", dense, (d, rank))
        w_gb = self.param("gate_up", dense, (rank, width))
        norm_scale = self.param("norm_scale", nn.initializers.ones_init(),
                                (d_h,))
        w_out = self.param("out_proj", dense, (width, d))
        chunk = rule_op.chunk_for(seq, self.chunk)
        _count_trace(h, d_h, rank, chunk)

        self.sow("intermediates", "kda_input", u)
        lead = u.shape[:-2]
        u = u.reshape(-1, seq, d).astype(self.dtype)
        cast = lambda w: w.astype(self.dtype)
        with jax.named_scope("kda_in_proj"):
            qkv = jnp.dot(u, cast(w_qkv))
            b = jnp.dot(u, cast(w_beta), preferred_element_type=STATE_DTYPE)
            a = jnp.dot(jnp.dot(u, cast(w_fa)), cast(w_fb),
                        preferred_element_type=STATE_DTYPE)
            z = jnp.dot(jnp.dot(u, cast(w_ga)), cast(w_gb))
        with jax.named_scope("kda_conv"):
            q, k, v = jnp.split(conv_op.causal_conv(qkv, conv_kernel), 3, -1)
        with jax.named_scope("kda_rule"):
            heads = lambda t: t.reshape(*t.shape[:-1], h, d_h)
            beta = jax.nn.sigmoid(b)
            # [b, s, H d] like q, k and v: the kernels read a head's columns
            g = -jnp.repeat(jnp.exp(a_log.astype(STATE_DTYPE)), d_h) * (
                jax.nn.softplus(a + dt_bias.astype(STATE_DTYPE)))
            q = norm_op.l2_norm(q, d_h, eps=L2_EPS, scale=d_h ** -0.5)
            k = norm_op.l2_norm(k, d_h, eps=L2_EPS)
            o = rule_op.channel_delta_rule(
                heads(q), heads(k), heads(v), heads(g), beta, chunk=chunk,
                state_dtype=STATE_DTYPE, precision=INVERSE_PRECISION)
        with jax.named_scope("kda_gate_norm"):
            y = norm_op.gated_norm(o.reshape(*o.shape[:-2], width), z,
                                   norm_scale, eps=self.norm_eps,
                                   gate="sigmoid")
        with jax.named_scope("kda_out_proj"):
            out = jnp.dot(y, cast(w_out))
        out = out.reshape(*lead, seq, d)
        self.sow("intermediates", "kda_output", out)
        return out


def kda_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``KimiDeltaAttention``: the heads are
    the tensor-parallel dimension. ``in_proj_qkv``'s and the convolution's
    columns interleave ``q | k | v`` and do not divide evenly over an axis,
    so they replicate, as ``gdn.gdn_leaf_spec`` has Gated DeltaNet's; the
    second matrices of the decay's and the gate's pairs are column-parallel
    by head, the per-head and per-channel vectors shard with them and
    ``out_proj``'s rows shard; the rank-wide first matrices and the norm's
    scale, one head's, replicate."""
    if name in ("dt_bias", "A_log"):
        return P(tp_axis)
    if name in ("decay_up", "gate_up", "in_proj_beta"):
        return P(None, tp_axis)
    if name == "out_proj":
        return P(tp_axis, None)
    return P()
