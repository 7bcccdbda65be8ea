"""VGG-16 and Inception V3 for the Horovod-parity benchmarks.

The reference's published scaling headline is Inception V3 and VGG-16
(``/root/reference/README.rst:96``, ``docs/benchmarks.rst:13-14``: 90%
scaling efficiency for Inception V3 / ResNet-101, 68% for VGG-16 at 512
GPUs) plus ResNet throughput. ``horovod_tpu/models/resnet.py`` covers
the ResNet family; this module completes the benchmark trio, so that
the same model mix can be built TPU-natively (no cell of the benchmark
runs these two: BENCHMARK.json).

TPU-first choices (same policy as resnet.py):
- NHWC layout throughout — XLA:TPU's native conv layout.
- bfloat16 activations/weights, fp32 master params.
- VGG uses the original architecture but with BatchNorm (the common
  modern variant — plain VGG's huge fp32 FC head would dominate HBM for
  no benchmark value; the classifier keeps the 4096-wide FCs).
- Inception V3 follows the canonical tower layout (torchvision
  inception.py structure: 5b/5c/5d mixed, 6a reduction, 6b-6e 7x7
  factorized towers, 7a reduction, 7b/7c expanded) with BN after every
  conv, aux head omitted (benchmarks train the main head only).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp

from .normalization import TpuBatchNorm

ModuleDef = Any


class _ConvBN(nn.Module):
    """conv → BN → ReLU, the building block of both models."""
    features: int
    kernel: Sequence[int] = (3, 3)
    strides: Sequence[int] = (1, 1)
    padding: Any = "SAME"
    dtype: Any = jnp.bfloat16
    norm_impl: str = "tpu"
    axis_name: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(self.features, tuple(self.kernel),
                    strides=tuple(self.strides), padding=self.padding,
                    use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32)(x)
        norm_cls = TpuBatchNorm if self.norm_impl == "tpu" else nn.BatchNorm
        x = norm_cls(use_running_average=not train, momentum=0.9,
                     epsilon=1e-3, dtype=self.dtype,
                     param_dtype=jnp.float32,
                     axis_name=self.axis_name)(x)
        return nn.relu(x)


class VGG16(nn.Module):
    """VGG-16 (configuration D) with BatchNorm.

    Reference benchmark subject (``docs/benchmarks.rst:14``: 68% scaling
    efficiency at 512 GPUs — VGG's fat dense head is the classic
    gradient-fusion stress test, which is exactly why Horovod benchmarks
    it: one 102M-parameter FC gradient dominates the allreduce)."""
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    norm_impl: str = "tpu"
    axis_name: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        cbn = partial(_ConvBN, dtype=self.dtype, norm_impl=self.norm_impl,
                      axis_name=self.axis_name)
        x = x.astype(self.dtype)
        for block, (features, convs) in enumerate(
                [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]):
            for i in range(convs):
                x = cbn(features, name=f"conv{block}_{i}")(x, train)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(4096, dtype=self.dtype,
                             param_dtype=jnp.float32, name="fc1")(x))
        x = nn.relu(nn.Dense(4096, dtype=self.dtype,
                             param_dtype=jnp.float32, name="fc2")(x))
        # fp32 logits for a stable softmax (same policy as resnet head)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        param_dtype=jnp.float32, name="head")(x)


class _InceptionTower(nn.Module):
    """One mixed block: parallel conv towers concatenated on channels."""
    towers: Sequence[Sequence[dict]]
    pool_features: int
    dtype: Any = jnp.bfloat16
    norm_impl: str = "tpu"
    axis_name: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        cbn = partial(_ConvBN, dtype=self.dtype, norm_impl=self.norm_impl,
                      axis_name=self.axis_name)
        outs = []
        for t, tower in enumerate(self.towers):
            h = x
            for c, spec in enumerate(tower):
                h = cbn(spec["features"], kernel=spec.get("kernel", (1, 1)),
                        strides=spec.get("strides", (1, 1)),
                        padding=spec.get("padding", "SAME"),
                        name=f"t{t}_c{c}")(h, train)
            outs.append(h)
        if self.pool_features:
            p = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
            outs.append(_ConvBN(self.pool_features, kernel=(1, 1),
                                dtype=self.dtype, norm_impl=self.norm_impl,
                                axis_name=self.axis_name,
                                name="pool_proj")(p, train))
        return jnp.concatenate(outs, axis=-1)


def _c(features, kernel=(1, 1), strides=(1, 1), padding="SAME"):
    return {"features": features, "kernel": kernel, "strides": strides,
            "padding": padding}


class InceptionV3(nn.Module):
    """Inception V3 (299×299 input), canonical tower layout, aux head
    omitted. Reference benchmark subject (``docs/benchmarks.rst:13``:
    90% scaling efficiency at 512 GPUs)."""
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    norm_impl: str = "tpu"
    axis_name: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        kw = dict(dtype=self.dtype, norm_impl=self.norm_impl,
                  axis_name=self.axis_name)
        cbn = partial(_ConvBN, **kw)
        mix = partial(_InceptionTower, **kw)
        x = x.astype(self.dtype)
        # stem: 299 → 35x35x192
        x = cbn(32, strides=(2, 2), padding="VALID", name="stem1")(x, train)
        x = cbn(32, padding="VALID", name="stem2")(x, train)
        x = cbn(64, name="stem3")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = cbn(80, kernel=(1, 1), padding="VALID", name="stem4")(x, train)
        x = cbn(192, padding="VALID", name="stem5")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        # 5b/5c/5d: 35x35 mixed, pool proj 32/64/64
        for i, pf in enumerate([32, 64, 64]):
            x = mix(towers=[
                [_c(64)],
                [_c(48), _c(64, kernel=(5, 5))],
                [_c(64), _c(96, kernel=(3, 3)), _c(96, kernel=(3, 3))],
            ], pool_features=pf, name=f"mixed5{'bcd'[i]}")(x, train)
        # 6a: reduction to 17x17
        x = jnp.concatenate([
            cbn(384, kernel=(3, 3), strides=(2, 2), padding="VALID",
                name="red6a_a")(x, train),
            cbn(96, kernel=(3, 3), strides=(2, 2), padding="VALID",
                name="red6a_b3")(
                cbn(96, kernel=(3, 3), name="red6a_b2")(
                    cbn(64, kernel=(1, 1), name="red6a_b1")(x, train), train), train),
            nn.max_pool(x, (3, 3), strides=(2, 2)),
        ], axis=-1)
        # 6b-6e: 17x17 factorized 7x1/1x7 towers
        for i, f7 in enumerate([128, 160, 160, 192]):
            x = mix(towers=[
                [_c(192)],
                [_c(f7), _c(f7, kernel=(1, 7)), _c(192, kernel=(7, 1))],
                [_c(f7), _c(f7, kernel=(7, 1)), _c(f7, kernel=(1, 7)),
                 _c(f7, kernel=(7, 1)), _c(192, kernel=(1, 7))],
            ], pool_features=192, name=f"mixed6{'bcde'[i]}")(x, train)
        # 7a: reduction to 8x8
        x = jnp.concatenate([
            cbn(320, kernel=(3, 3), strides=(2, 2), padding="VALID",
                name="red7a_a2")(
                cbn(192, kernel=(1, 1), name="red7a_a1")(x, train), train),
            cbn(192, kernel=(3, 3), strides=(2, 2), padding="VALID",
                name="red7a_b4")(
                cbn(192, kernel=(1, 7), name="red7a_b3")(
                    cbn(192, kernel=(7, 1), name="red7a_b2")(
                        cbn(192, kernel=(1, 1), name="red7a_b1")(x, train), train),
                    train), train),
            nn.max_pool(x, (3, 3), strides=(2, 2)),
        ], axis=-1)
        # 7b/7c: 8x8 expanded towers (3x3 split into 1x3 + 3x1 branches)
        for i in range(2):
            y1 = cbn(384, kernel=(1, 1), name=f"m7{'bc'[i]}_b1")(x, train)
            y1 = jnp.concatenate([
                cbn(384, kernel=(1, 3), name=f"m7{'bc'[i]}_b1a")(y1, train),
                cbn(384, kernel=(3, 1), name=f"m7{'bc'[i]}_b1b")(y1, train),
            ], axis=-1)
            y2 = cbn(448, kernel=(1, 1), name=f"m7{'bc'[i]}_b2")(x, train)
            y2 = cbn(384, kernel=(3, 3), name=f"m7{'bc'[i]}_b2a")(y2, train)
            y2 = jnp.concatenate([
                cbn(384, kernel=(1, 3), name=f"m7{'bc'[i]}_b2b")(y2, train),
                cbn(384, kernel=(3, 1), name=f"m7{'bc'[i]}_b2c")(y2, train),
            ], axis=-1)
            p = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
            p = cbn(192, kernel=(1, 1), name=f"m7{'bc'[i]}_pool")(p, train)
            x = jnp.concatenate(
                [cbn(320, kernel=(1, 1), name=f"m7{'bc'[i]}_b0")(x, train), y1, y2, p],
                axis=-1)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        param_dtype=jnp.float32, name="head")(x)
