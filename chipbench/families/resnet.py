"""The ResNet family: ``horovod_tpu.models.ResNet`` with the package's
defaults (no ``norm_impl``, ``conv0_space_to_depth`` or other option is
passed: an optimisation has to become the default to count).

Configuration keys: ``stage_sizes``, ``width``, ``num_classes``,
``dtype`` and ``optimizer`` ({"name": "sgd", "learning_rate": ...,
"momentum": ...}). Traffic keys: ``per_chip_batch``, ``image_size``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import ResNet

from chipbench import flops
from chipbench.families import Job, optimizer_from
from chipbench.reference import resnet as reference

REHEARSAL = {"config": {"width": 8, "num_classes": 10},
             "traffic": {"image_size": 32, "per_chip_batch": 8}}

# Program (bf16 convolutions and activations, f32 BatchNorm statistics,
# f32 head) against the float32 reference, on the parameters a window of
# training left and the whole batch it trained on. Every activation is
# rounded to bf16 (eps 3.9e-3) some 50 layers deep; BatchNorm
# renormalises after each, so errors do not grow with depth, and the loss
# is a mean over the batch. Measured 0.9e-3 to 1.2e-3 apart on the chip
# over 14 seeds (PERF.md, PR 22), the program always the higher: the
# parameters were fitted to its rounding. A wrong stride, padding or
# statistic, or a dropped branch, moves it by 1e-2 and more.
LOSS_REL_BOUND = 5e-3


def build(config: dict, traffic: dict) -> Job:
    size, batch = traffic["image_size"], traffic["per_chip_batch"]
    dtype = jnp.dtype(config["dtype"])
    model = ResNet(stage_sizes=config["stage_sizes"], width=config["width"],
                   num_classes=config["num_classes"], dtype=dtype)

    def init(key):
        variables = model.init(key, jnp.zeros((1, size, size, 3), dtype),
                               train=True)
        return variables["params"], variables["batch_stats"]

    def make_batch(key, n_chips):
        k_img, k_lab = jax.random.split(key)
        n = n_chips * batch
        return (jax.random.normal(k_img, (n, size, size, 3), dtype),
                jax.random.randint(k_lab, (n,), 0, config["num_classes"],
                                   jnp.int32))

    def loss(params, batch_stats, data):
        images, labels = data
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        return ce.mean(), mutated["batch_stats"]

    def reference_loss(params, batch_stats, data):
        return reference.loss(params, *data,
                              stage_sizes=tuple(config["stage_sizes"]))

    shapes = jax.eval_shape(init, jax.random.key(0))
    macs = flops.forward_macs(
        lambda p, s, x: model.apply({"params": p, "batch_stats": s}, x,
                                    train=True, mutable=["batch_stats"]),
        *shapes, jax.ShapeDtypeStruct((1, size, size, 3), dtype))
    job = Job(item="images", items_per_step_per_chip=batch,
              flops_per_item=flops.train_flops_from_forward_macs(macs),
              init=init, make_batch=make_batch, loss=loss,
              optimizer=lambda: optimizer_from(config["optimizer"]),
              reference_loss=reference_loss,
              loss_rel_bound=LOSS_REL_BOUND, check=lambda key: [],
              facts={"forward_macs_per_image": macs})
    job.probe = job
    return job
