"""Plain reference of the ResNet v1.5 the ``resnet`` family runs: the
training-mode forward pass (BatchNorm over batch statistics) and loss in
float32 ``lax``/``jax.numpy``, no flax. It reads the package's parameter
tree as data and shares no code with ``horovod_tpu.models``.

The network, as the package builds it: 7x7/2 stem (padding 3), BatchNorm,
relu, 3x3/2 max pool (padding 1); stages of bottleneck blocks (1x1, 3x3
carrying the stride, 1x1 to four times the width; 'SAME' padding; a 1x1
strided projection where the shape changes); global mean; dense head
with bias. BatchNorm: biased variance as E[x^2] - E[x]^2 clamped at 0,
epsilon 1e-5. Loss = mean cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, kernel, stride, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, eps=1e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.maximum(jnp.mean(x * x, (0, 1, 2)) - mean * mean, 0.0)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _norm(p, i):
    """The block's i-th norm, whatever class the package built it from
    (``TpuBatchNorm_1``, ``BatchNorm_1``): the tree is read as data."""
    (name,) = [k for k in p if k.endswith(f"BatchNorm_{i}")]
    return p[name]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"], 1, "SAME"),
                        _norm(p, 0)))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride, "SAME"),
                        _norm(p, 1)))
    y = _bn(_conv(y, p["Conv_2"]["kernel"], 1, "SAME"), _norm(p, 2))
    if "proj_conv" in p:
        x = _bn(_conv(x, p["proj_conv"]["kernel"], stride, "SAME"),
                p["proj_norm"])
    return jax.nn.relu(x + y)


def logits(params, images, stage_sizes):
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = images.astype(jnp.float32)
    x = _conv(x, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    i = 0
    for stage, n_blocks in enumerate(stage_sizes):
        for j in range(n_blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            x = _bottleneck(x, params[f"BottleneckBlock_{i}"], stride)
            i += 1
    x = jnp.mean(x, (1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"]


def loss(params, images, labels, stage_sizes=(3, 4, 6, 3)) -> float:
    """Mean cross-entropy of the training-mode forward pass over the
    whole batch (its BatchNorm statistics are the batch's)."""
    def fn(params, images, labels):
        z = logits(params, images, stage_sizes)
        picked = jnp.take_along_axis(z, labels[:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(z, -1) - picked)

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(fn)(params, images, labels))
