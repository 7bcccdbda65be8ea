"""ResNet for the Horovod-parity benchmarks.

The reference benchmarks data-parallel ResNet-50/101 throughput
(``examples/pytorch/pytorch_synthetic_benchmark.py``,
``docs/benchmarks.rst:28-43``); this is the TPU-native model used by
the benchmark's cell ``resnet50-b256`` (BENCHMARK.json) and the examples.

TPU-first choices:
- NHWC layout (XLA:TPU's native conv layout — channels last feeds the MXU
  without transposes).
- bfloat16 activations/weights with fp32 BatchNorm statistics and fp32
  residual adds where it matters for accuracy.
- ``BatchNorm(axis_name=...)`` gives cross-replica (synchronized) batch
  norm — the parity feature the reference implements by hand with
  allreduces of mean/var (``horovod/tensorflow/sync_batch_norm.py:22``,
  ``horovod/torch/sync_batch_norm.py``); on TPU it is one flag because the
  collective is compiled into the program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

from .normalization import TpuBatchNorm

ModuleDef = Any


class _SpaceToDepthStem(nn.Module):
    """MXU-friendly drop-in for the 7x7/2 stem conv.

    The stem convolution has 3 input channels — the MXU's contraction
    lanes run nearly empty there, and on TPU the stem is a measurable
    slice of the whole ResNet step. The classic TPU fix (public MLPerf
    ResNet submissions) is space-to-depth: fold a 2x2 pixel block into
    the channel dim (224x224x3 -> 112x112x12) and apply the SAME
    weights as an equivalent 4x4 stride-1 convolution. This is a pure
    reindexing of the 7x7 stride-2 conv — numerically identical, pinned
    by tests/test_models_vision.py — with 4x the input channels per MXU
    pass.

    The parameter keeps the standard ``(7, 7, 3, width)`` shape and the
    ``{"conv_init": {"kernel"}}`` checkpoint layout of the ``nn.Conv``
    it replaces; the kernel is rearranged at trace time (the rearrange
    is fused into the weight convert XLA already performs).
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        if h % 2 or w % 2:
            raise ValueError(
                f"conv0_space_to_depth requires even input height/width "
                f"(the stem folds 2x2 pixel blocks into channels) but got "
                f"{h}x{w}; pad the input to even dimensions or build the "
                f"model with conv0_space_to_depth=False for the standard "
                f"7x7/2 stem")
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (7, 7, c, self.features), jnp.float32)
        # pixels: (B, H, W, C) -> (B, H/2, W/2, 2*2*C), block-major (a, b, c)
        x2 = x.reshape(b, h // 2, 2, w // 2, 2, c)
        x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2,
                                                    4 * c)
        # weights: pad 7x7 -> 8x8 with one LEADING zero row/col so tap
        # u maps to (dp, a) via u + 1 = 2*dp + a, then split each dim
        # into (block, parity) and fold parity into channels
        k = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k = k.reshape(4, 2, 4, 2, c, self.features)
        k = k.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c,
                                                  self.features)
        # output i consumes folded rows i-2..i+1 -> padding (2, 1)
        return lax.conv_general_dilated(
            x2.astype(self.dtype), k.astype(self.dtype),
            window_strides=(1, 1), padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


class BottleneckBlock(nn.Module):
    """Standard bottleneck residual block (1x1 → 3x3 → 1x1, expansion 4)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), strides=(self.strides,
                                                     self.strides))(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # zero-init the last BN scale: residual branch starts as identity
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 strides=(self.strides, self.strides),
                                 name="proj_conv")(residual)
            residual = self.norm(name="proj_norm")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    """ResNet v1.5 (stride-2 in the 3x3, like the reference torchvision
    models the benchmarks use)."""

    stage_sizes: Sequence[int]
    num_classes: int = 1000
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    axis_name: Optional[str] = None  # set → synchronized batch norm
    # "tpu": TpuBatchNorm — bf16 HBM traffic, fp32-accumulated statistics
    # (see models/normalization.py); "flax": stock nn.BatchNorm (fp32
    # statistics AND fp32 normalization passes) kept for parity checks.
    norm_impl: str = "tpu"
    # Replace the 3-input-channel 7x7/2 stem with the numerically
    # identical space-to-depth 4x4 form (see _SpaceToDepthStem). Same
    # parameter shape and checkpoint layout either way.
    conv0_space_to_depth: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        if self.norm_impl not in ("tpu", "flax"):
            raise ValueError(f"norm_impl must be 'tpu' or 'flax', got "
                             f"{self.norm_impl!r}")
        norm_cls = TpuBatchNorm if self.norm_impl == "tpu" else nn.BatchNorm
        norm = partial(norm_cls, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       param_dtype=jnp.float32, axis_name=self.axis_name)
        act = nn.relu

        x = x.astype(self.dtype)
        if self.conv0_space_to_depth:
            x = _SpaceToDepthStem(self.width, dtype=self.dtype,
                                  name="conv_init")(x)
        else:
            x = conv(self.width, (7, 7), strides=(2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                x = BottleneckBlock(self.width * 2 ** i, strides=strides,
                                    conv=conv, norm=norm, act=act)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=jnp.float32, name="head")(x)
        return x


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])
