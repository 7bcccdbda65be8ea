"""Mamba-1 mixer (Gu and Dao, arXiv:2312.00752) and the gated memory unit
that reads its scan output layers later (SambaY; Ren et al.,
arXiv:2507.06607): the state-space layers of a decoder-hybrid-decoder
model.

**``Mamba1Mixer``.** For an input ``x [batch, seq, d]``, an inner width
``D = expand x d``, a state of ``N`` a channel and a step rank ``R``
(``ceil(d / 16)`` where none is named), the layer is six steps, each under
a ``jax.named_scope`` of its name so that a device trace can be split by
them:

1. ``mamba_in_proj``: ``[u | z] = x W_in``, widths ``D`` and ``D``; one
   product, no bias.
2. ``mamba_conv``: ``u = silu(conv(u) + b_c)``, a causal depthwise
   convolution of ``conv`` taps over the sequence
   (``ops/causal_conv.py``, by its kernels where its rule says so).
3. ``mamba_step``: ``[r | B | C] = u W_x`` (widths ``R``, ``N``, ``N``, no
   bias) and ``delta = softplus(r W_dt + b_dt)``, float32.
4. ``mamba_scan``: ``a = -exp(A_log)`` ``[D, N]``, float32; a state ``h [D,
   N]`` a sequence,

       h_t = exp(delta_t[:, None] a) h_{t-1} + (delta_t u_t)[:, None] B_t[None, :]
       m_t = h_t C_t + D_skip u_t

   (``ops/selective_scan.py``: decays, state and the sum over the state in
   float32, ``DECAY_DTYPE``; the sequence walked in chunks with the state
   carried, nothing of ``[seq, D, N]`` held).
5. ``mamba_gate``: ``m * silu(z)``, float32.
6. ``mamba_out_proj``: ``(m * silu(z)) W_out``, no bias.

The mixer returns ``(out, m)``: **``m`` before the gate is the memory** a
later layer's unit reads.

**``GatedMemoryUnit``.** ``out = (m * silu(x W_1)) W_2`` with ``m`` the
memory of an earlier layer at the same position, ``W_1 [d, D]``, ``W_2 [D,
d]``, no bias; ``D`` is the memory's own width. Three scopes:
``gmu_in_proj``, ``gmu_gate`` (float32), ``gmu_out_proj``.

For a caller that asks for the collection ``intermediates`` a layer's own
input and output are sown there (``mamba_input``, ``mamba_output``,
``mamba_memory``; ``gmu_input``, ``gmu_memory``, ``gmu_output``), for a
comparison with a position-by-position reference on the same input.
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
from horovod_tpu.ops import selective_scan as scan_op

# What the step size, the decays and the carried state are computed in,
# whatever the products run in. A module constant and no option: a test or
# a builder's experiment steers it from outside.
DECAY_DTYPE = jnp.float32
# Mamba's own initialisation of the step size: dt log-uniform in [DT_MIN,
# DT_MAX], floored at DT_FLOOR, stored as its inverse softplus.
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def _count_trace(channels, state, chunk, body):
    """One count a traced layer; ``body`` the scan's (``plain``,
    ``kernels``)."""
    _pallas.count_trace(
        "hvt_mamba_layers_traced_total",
        "state-space (Mamba-1) layers traced into compiled programs, by "
        "the body their selective scan takes (counted per trace, not per "
        "execution)",
        channels=channels, state=state, chunk=chunk, body=body)


def step_rank(d_model: int, rank: int = 0) -> int:
    """The rank of the step size's two-matrix projection: ``rank``, or
    Mamba's own ``ceil(d / 16)`` where it is 0."""
    return rank or math.ceil(d_model / 16)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)    # softplus^-1


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-1's own: ``A[c, n] = n + 1`` for every channel, stored as its
    logarithm."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1,
                                               dtype=jnp.float32)),
                            shape).astype(dtype)


class Mamba1Mixer(nn.Module):
    """The mixer. Parameters: ``in_proj [d, 2 D]`` (columns ``u | z``),
    ``conv_kernel [taps, D]`` and ``conv_bias [D]``, ``x_proj [D, R + 2
    N]`` (columns ``r | B | C``), ``dt_proj [R, D]`` and ``dt_bias [D]``,
    ``A_log [D, N]``, ``D_skip [D]``, ``out_proj [D, d]``."""

    expand: int = 2
    state: int = 16
    conv: int = 4
    rank: int = 0
    chunk: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d, seq = x.shape[-1], x.shape[-2]
        inner, n, rank = self.expand * d, self.state, step_rank(d, self.rank)
        dense = nn.initializers.lecun_normal()
        w_in = self.param("in_proj", dense, (d, 2 * inner))
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (self.conv, inner))
        conv_bias = self.param("conv_bias", nn.initializers.zeros_init(),
                               (inner,))
        w_x = self.param("x_proj", dense, (inner, rank + 2 * n))
        # Mamba's own: uniform in +- rank^-1/2, so that the step size
        # starts from what dt_bias gives it
        w_dt = self.param("dt_proj", nn.initializers.variance_scaling(
            1.0 / 3.0, "fan_in", "uniform"), (rank, inner))
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        a_log = self.param("A_log", _a_log_init, (inner, n))
        d_skip = self.param("D_skip", nn.initializers.ones_init(), (inner,))
        w_out = self.param("out_proj", dense, (inner, d))
        chunk = scan_op.chunk_for(seq, self.chunk)
        _count_trace(inner, n, chunk, "kernels" if scan_op.serves(
            seq, inner, n, chunk, self.dtype, DECAY_DTYPE) else "plain")

        self.sow("intermediates", "mamba_input", x)
        lead = x.shape[:-2]
        x = x.reshape(-1, seq, d).astype(self.dtype)
        with jax.named_scope("mamba_in_proj"):
            u, z = jnp.split(jnp.dot(x, w_in.astype(self.dtype)), 2, -1)
        with jax.named_scope("mamba_conv"):
            u = conv_op.causal_conv(u, conv_kernel, conv_bias)
        with jax.named_scope("mamba_step"):
            r, b, c = jnp.split(jnp.dot(u, w_x.astype(self.dtype)),
                                [rank, rank + n], -1)
            delta = jax.nn.softplus(
                jnp.dot(r, w_dt.astype(self.dtype),
                        preferred_element_type=DECAY_DTYPE)
                + dt_bias.astype(DECAY_DTYPE))
        with jax.named_scope("mamba_scan"):
            a = -jnp.exp(a_log.astype(DECAY_DTYPE))
            m = scan_op.selective_scan(u, delta, a, b, c, chunk=self.chunk,
                                       state_dtype=DECAY_DTYPE)
            m = (m.astype(jnp.float32) + u.astype(jnp.float32)
                 * d_skip.astype(jnp.float32)).astype(self.dtype)
        with jax.named_scope("mamba_gate"):
            y = (m.astype(jnp.float32) * jax.nn.silu(
                z.astype(jnp.float32))).astype(self.dtype)
        with jax.named_scope("mamba_out_proj"):
            out = jnp.dot(y, w_out.astype(self.dtype))
        out, m = out.reshape(*lead, seq, d), m.reshape(*lead, seq, inner)
        self.sow("intermediates", "mamba_memory", m)
        self.sow("intermediates", "mamba_output", out)
        return out, m


def mamba_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``Mamba1Mixer``: the channels are the
    tensor-parallel dimension. ``in_proj``'s columns are ``u | z`` and do
    not divide evenly over an axis, so they replicate here, as Mamba-2's
    do; the convolution, the step's second matrix and the vectors a channel
    go by channel, ``x_proj`` and ``out_proj`` are row-parallel over them
    (one sum each)."""
    if name in ("conv_bias", "dt_bias", "D_skip"):
        return P(tp_axis)
    if name in ("conv_kernel", "dt_proj"):
        return P(None, tp_axis)
    if name in ("x_proj", "out_proj", "A_log"):
        return P(tp_axis, None)
    return P()


class GatedMemoryUnit(nn.Module):
    """The unit. Parameters: ``in_proj [d, D]``, ``out_proj [D, d]``, ``D``
    the width of the memory it is handed."""

    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, memory):
        d, inner = x.shape[-1], memory.shape[-1]
        dense = nn.initializers.lecun_normal()
        w_in = self.param("in_proj", dense, (d, inner))
        w_out = self.param("out_proj", dense, (inner, d))
        self.sow("intermediates", "gmu_input", x)
        self.sow("intermediates", "gmu_memory", memory)
        with jax.named_scope("gmu_in_proj"):
            gate = jnp.dot(x.astype(self.dtype), w_in.astype(self.dtype))
        with jax.named_scope("gmu_gate"):
            y = (memory.astype(jnp.float32) * jax.nn.silu(
                gate.astype(jnp.float32))).astype(self.dtype)
        with jax.named_scope("gmu_out_proj"):
            out = jnp.dot(y, w_out.astype(self.dtype))
        self.sow("intermediates", "gmu_output", out)
        return out


def gmu_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``GatedMemoryUnit``: column-parallel
    in, row-parallel out over the memory's channels."""
    return {"in_proj": P(None, tp_axis),
            "out_proj": P(tp_axis, None)}.get(name, P())
