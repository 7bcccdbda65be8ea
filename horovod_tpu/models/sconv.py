"""Gated short-convolution mixer (the ``conv`` layers of Liquid AI's LFM2
family): a depthwise causal convolution of a few taps between two
elementwise gates that the input itself makes, linear in the sequence and
with no state beyond the last ``taps - 1`` positions.

For an input ``x [batch, seq, d]`` the layer is three steps, each under a
``jax.named_scope`` of its name so that a device trace can be split by
them:

1. ``sconv_in_proj``: ``[B | C | u] = x W_in``, three runs of ``d``
   columns; one product, no bias.
2. ``sconv_gate_conv``: ``v = B * u``; ``c_t = sum_j w_j v_{t - taps + 1
   + j}`` a channel (zeros before the sequence, no bias, **no
   activation**); ``y = C * c``. Float32 inside, the layer's ``dtype`` in
   and out (``gated_conv``). The taps are ``causal_conv_plain``'s
   (``ops/causal_conv.py``), the package's one plain convolution, told to
   leave its ``silu`` out; that module's Pallas kernels compute
   ``silu(conv(x) + b)`` alone and are not called: a kernel that reads
   ``B``, ``C`` and ``u`` once and writes ``y`` once is not written yet
   (PERF.md section 7 prices it).
3. ``sconv_out_proj``: ``y W_out``, no bias.

For a caller that asks for the collection ``intermediates`` the mixer's
own input and output are sown there (``sconv_input``, ``sconv_output``),
for a comparison with a position-by-position reference on the same input.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import _pallas
from horovod_tpu.ops import causal_conv as conv_op


def _count_trace(channels, taps):
    """One count a traced layer."""
    _pallas.count_trace(
        "hvt_sconv_layers_traced_total",
        "gated short-convolution layers traced into compiled programs "
        "(counted per trace, not per execution)",
        channels=channels, taps=taps)


def gated_conv(b, c, u, weight):
    """``c * conv(b * u)``: ``b``, ``c``, ``u`` ``[batch, s, channels]``,
    ``weight [taps, channels]``; position t of the convolution is ``sum_j
    weight[j] v[t - taps + 1 + j]`` (zeros before the sequence). Float32
    inside, ``u.dtype`` out."""
    v = b.astype(jnp.float32) * u.astype(jnp.float32)
    conv = conv_op.causal_conv_plain(v, weight, activation=None)
    return (c.astype(jnp.float32) * conv).astype(u.dtype)


class ShortConv(nn.Module):
    """The mixer. Parameters: ``in_proj [d, 3, d]`` (the columns of ``W_in``
    as ``B | C | u``, a run an index of the middle axis, so that the
    channels are an axis of their own), ``conv_kernel [taps, d]``,
    ``out_proj [d, d]``."""

    taps: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d, seq = x.shape[-1], x.shape[-2]
        dense = nn.initializers.normal(0.02)
        w_in = self.param("in_proj", dense, (d, 3, d))
        conv_kernel = self.param(
            "conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (self.taps, d))
        w_out = self.param("out_proj", dense, (d, d))
        _count_trace(d, self.taps)

        self.sow("intermediates", "sconv_input", x)
        lead = x.shape[:-2]
        x = x.reshape(-1, seq, d).astype(self.dtype)
        with jax.named_scope("sconv_in_proj"):
            b, c, u = jnp.split(
                jnp.dot(x, w_in.reshape(d, 3 * d).astype(self.dtype)), 3, -1)
        with jax.named_scope("sconv_gate_conv"):
            y = gated_conv(b, c, u, conv_kernel)
        with jax.named_scope("sconv_out_proj"):
            out = jnp.dot(y, w_out.astype(self.dtype))
        out = out.reshape(*lead, seq, d)
        self.sow("intermediates", "sconv_output", out)
        return out


def sconv_leaf_spec(name: str, tp_axis):
    """PartitionSpec of one leaf of a ``ShortConv``: the channels are the
    tensor-parallel dimension. ``in_proj`` is column-parallel over them
    (each of ``B``, ``C`` and ``u`` its own run, so a chip holds the same
    channels of all three and the gates stay local), the taps go by
    channel, and ``out_proj`` is row-parallel: one sum a layer."""
    return {"in_proj": P(None, None, tp_axis),
            "conv_kernel": P(None, tp_axis),
            "out_proj": P(tp_axis, None)}.get(name, P())
