"""TPU-first batch normalization.

``flax.linen.BatchNorm`` promotes the activation tensor to float32 both
for the statistics pass and for the normalization pass. On TPU that means
two extra full fp32 elementwise sweeps over HBM per layer (an earlier
builder's reason for this module; the two have not been timed against
each other on the benchmark's chip: ROADMAP S3). ``TpuBatchNorm`` keeps the fp32 *accuracy* contract of the
reference's recipes (fp16 training with fp32 BN statistics — e.g.
``horovod/torch/sync_batch_norm.py`` keeps stats in fp32) while keeping
the HBM traffic in bf16:

- The statistics reductions consume the bf16 activations directly; the
  f32 convert is element-wise inside the reduce's input fusion, so XLA
  reads bf16 from HBM and accumulates in fp32 registers — no fp32 copy
  of the activations is ever materialized.
- mean / var / scale / bias are folded into a per-channel multiply-add
  (``y = x * mul + shift``) computed in fp32 at channel granularity
  (C elements, trivially cheap) and applied to the activations in bf16 —
  one bf16 elementwise pass that XLA fuses into the neighboring conv.
- Running statistics stay fp32, exactly like the reference.
- ``axis_name`` gives synchronized (cross-replica) batch norm via a
  compiled ``lax.pmean`` over the raw moments — the parity feature the
  reference implements by hand with allreduces of mean/var
  (``horovod/tensorflow/sync_batch_norm.py:22``).

Numerics: identical formula to flax's ``use_fast_variance=True`` path
(var = E[x²] − E[x]²), same "batch_stats" collection layout
({"mean", "var"}), so checkpoints and parity tests interoperate.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

Initializer = Callable[..., Any]


class TpuBatchNorm(nn.Module):
    """BatchNorm with bf16 HBM traffic and fp32 accumulation/statistics.

    Drop-in for ``flax.linen.BatchNorm`` over channels-last inputs (the
    XLA:TPU-native layout): same constructor surface for the arguments
    the models use, same ``batch_stats`` variable collection.
    """

    use_running_average: Optional[bool] = None
    momentum: float = 0.99
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    use_bias: bool = True
    use_scale: bool = True
    bias_init: Initializer = nn.initializers.zeros_init()
    scale_init: Initializer = nn.initializers.ones_init()
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        use_running_average = nn.merge_param(
            "use_running_average", self.use_running_average,
            use_running_average)
        num_features = x.shape[-1]
        reduction_axes = tuple(range(x.ndim - 1))

        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), (num_features,))
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), (num_features,))
        scale = (self.param("scale", self.scale_init, (num_features,),
                            self.param_dtype) if self.use_scale else None)
        bias = (self.param("bias", self.bias_init, (num_features,),
                           self.param_dtype) if self.use_bias else None)

        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            # Element-wise convert feeding straight into the reduces: XLA
            # fuses it, so the activations are read from HBM in bf16 and
            # accumulated in fp32. Both moments share one input fusion.
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, reduction_axes)
            mean2 = jnp.mean(jnp.square(xf), reduction_axes)
            if self.axis_name is not None and not self.is_initializing():
                mean, mean2 = lax.pmean((mean, mean2),
                                        axis_name=self.axis_name)
            # fast variance (flax's default formula); clamp the fp32
            # cancellation residue at zero
            var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
                ra_var.value = m * ra_var.value + (1.0 - m) * var

        # Fold everything into one per-channel affine, computed at channel
        # granularity in fp32 and applied in the storage dtype: a single
        # bf16 elementwise pass, fusable into the adjacent conv.
        mul = lax.rsqrt(var + jnp.float32(self.epsilon))
        if scale is not None:
            mul = mul * scale.astype(jnp.float32)
        shift = -mean * mul
        if bias is not None:
            shift = shift + bias.astype(jnp.float32)
        out_dtype = self.dtype or x.dtype
        return (x.astype(out_dtype) * mul.astype(out_dtype)
                + shift.astype(out_dtype))
