"""Each plain reference against the package's model, tiny, on the CPU,
in float32 (where the two must agree to rounding)."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare
from chipbench.families import gpt, resnet
from chipbench.reference import gpt as gpt_reference


def test_gpt_reference_loss_and_gradient():
    config = {"vocab_size": 97, "n_layer": 3, "n_embd": 32, "n_head": 4,
              "n_inner": 64, "dtype": "float32", "remat": True,
              "use_flash": "auto",
              "optimizer": {"name": "adamw", "learning_rate": 1e-3}}
    job = gpt.build(config, {"seq_len": 24, "per_chip_batch": 3})
    params, extra = jax.jit(job.init)(jax.random.key(0))
    # the initial norms and embedding are too regular to catch a wrong
    # pairing: perturb every leaf
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = tree.unflatten([a + 0.1 * jax.random.normal(k, a.shape)
                             for a, k in zip(leaves, keys)])
    tokens = job.make_batch(jax.random.key(2), 1)
    (got, _), got_grad = jax.value_and_grad(job.loss, has_aux=True)(
        params, extra, tokens)
    want, want_grad = gpt_reference.loss_and_grad(params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert compare.rel_l2(got_grad, want_grad) < 1e-5


def test_gpt_family_check_passes_and_catches_a_wrong_program():
    config = {"vocab_size": 97, "n_layer": 3, "n_embd": 32, "n_head": 4,
              "n_inner": 64, "dtype": "float32", "remat": False,
              "use_flash": False,
              "optimizer": {"name": "adamw", "learning_rate": 1e-3}}
    job = gpt.build(config, {"seq_len": 24, "per_chip_batch": 3})
    params, extra = jax.jit(job.init)(jax.random.key(0))
    tokens = job.make_batch(jax.random.key(2), 1)
    # the block-at-a-time forward is the same arithmetic as the one
    # jax.grad differentiates
    want, _ = gpt_reference.loss_and_grad(params, tokens)
    np.testing.assert_allclose(job.reference_loss(params, extra, tokens),
                               want, rtol=1e-6)
    assert all(c.ok for c in job.check(jax.random.key(3)))
    honest = job.probe.loss
    job.probe.loss = lambda p, e, t: (honest(p, e, t)[0] * 1.05, e)
    assert not job.check(jax.random.key(3))[0].ok


def test_resnet_reference_loss():
    config = {"stage_sizes": [2, 1, 1], "width": 8, "num_classes": 10,
              "dtype": "float32",
              "optimizer": {"name": "sgd", "learning_rate": 0.01}}
    job = resnet.build(config, {"image_size": 32, "per_chip_batch": 8})
    params, stats = jax.jit(job.init)(jax.random.key(0))
    # every branch must count: lift the zero-initialised scales and biases
    params = jax.tree.map(lambda a: a + 0.5 if a.ndim == 1 else a, params)
    data = job.make_batch(jax.random.key(1), 1)
    got, _ = job.loss(params, stats, data)
    want = job.reference_loss(params, stats, data)
    # float32 against float32: far inside the bf16 bound
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert job.loss_rel_bound >= 1e-4
