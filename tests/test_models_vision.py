"""The vision models (ResNet, VGG-16, Inception V3), the TPU batch norm and
the driver's graft entry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _init(model, x, seed=0, **kwargs):
    """`model.init` as one program: eager, every operation of the
    initialisation is a compile of its own."""
    return jax.jit(lambda key, x: model.init(key, x, **kwargs))(
        jax.random.PRNGKey(seed), x)


def test_resnet50_forward_shape():
    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables = _init(model, x, train=True)
    logits, mutated = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables, x)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32
    assert "batch_stats" in mutated


def test_conv0_space_to_depth_is_numerically_identical():
    """The s2d stem is a pure reindexing of the 7x7/2 conv: same kernel
    parameter, same output, for any input — and the checkpoint layout
    ({"conv_init": {"kernel"}}, shape (7,7,3,width)) is unchanged."""
    from horovod_tpu.models.resnet import _SpaceToDepthStem
    from jax import lax

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
    stem = _SpaceToDepthStem(features=16, dtype=jnp.float32)
    variables = stem.init(jax.random.PRNGKey(1), x)
    k = variables["params"]["kernel"]
    assert k.shape == (7, 7, 3, 16)

    got = stem.apply(variables, x)
    want = lax.conv_general_dilated(
        x, k, window_strides=(2, 2), padding=((3, 3), (3, 3)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert got.shape == want.shape == (2, 16, 16, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_resnet_conv0_s2d_checkpoint_layout_matches_standard_stem():
    from horovod_tpu.models import ResNet50

    x = jnp.zeros((1, 64, 64, 3))
    # only shapes and the tree are read
    std, s2d = (
        jax.eval_shape(lambda: ResNet50(
            num_classes=10, dtype=jnp.float32, **stem).init(
                jax.random.PRNGKey(0), x, train=True))
        for stem in ({}, {"conv0_space_to_depth": True}))
    assert (std["params"]["conv_init"]["kernel"].shape
            == s2d["params"]["conv_init"]["kernel"].shape)
    # a standard-stem checkpoint loads into an s2d model verbatim
    std_tree = jax.tree.structure(std)
    s2d_tree = jax.tree.structure(s2d)
    assert std_tree == s2d_tree


def test_resnet_eval_mode():
    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables = _init(model, x, train=True)
    logits = jax.jit(lambda v, x: model.apply(v, x, train=False))(
        variables, x)
    assert logits.shape == (2, 10)


def test_graft_entry_single_chip():
    import __graft_entry__ as ge

    fwd, (params, tokens) = ge.entry()
    logits = jax.jit(fwd)(params, tokens)
    assert logits.shape[:2] == tokens.shape
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_graft_dryrun_multichip_8():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_mesh_factors():
    import __graft_entry__ as ge

    for n in (1, 2, 4, 8, 16, 64, 256):
        dp, sp, tp = ge._mesh_factors(n)
        assert dp * sp * tp == n


class TestTpuBatchNorm:
    """TpuBatchNorm must be a pure performance rewrite of nn.BatchNorm:
    same formula (fast variance), same batch_stats layout, same numerics
    in fp32, same loss trajectory in bf16 (see models/normalization.py)."""

    def _pair(self, use_running_average=False):
        import flax.linen as nn

        from horovod_tpu.models.normalization import TpuBatchNorm

        kw = dict(use_running_average=use_running_average, momentum=0.9,
                  epsilon=1e-5, dtype=jnp.float32,
                  param_dtype=jnp.float32)
        return TpuBatchNorm(**kw), nn.BatchNorm(**kw)

    def test_forward_and_stats_match_flax_fp32(self):
        tpu_bn, flax_bn = self._pair()
        x = jnp.asarray(np.random.RandomState(0).randn(4, 5, 5, 7) * 3 + 1,
                        jnp.float32)
        v_t = tpu_bn.init(jax.random.PRNGKey(0), x)
        v_f = flax_bn.init(jax.random.PRNGKey(0), x)
        y_t, m_t = tpu_bn.apply(v_t, x, mutable=["batch_stats"])
        y_f, m_f = flax_bn.apply(v_f, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_f),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(m_t), jax.tree.leaves(m_f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_grads_match_flax_fp32(self):
        tpu_bn, flax_bn = self._pair()
        x = jnp.asarray(np.random.RandomState(1).randn(8, 3, 3, 4),
                        jnp.float32)
        v = flax_bn.init(jax.random.PRNGKey(0), x)

        def loss(mod, params, x):
            y, _ = mod.apply({"params": params,
                              "batch_stats": v["batch_stats"]}, x,
                             mutable=["batch_stats"])
            return (y ** 2).mean()

        for argnum in (1, 2):
            g_t = jax.grad(lambda p, xx: loss(tpu_bn, p, xx),
                           argnums=argnum - 1)(v["params"], x)
            g_f = jax.grad(lambda p, xx: loss(flax_bn, p, xx),
                           argnums=argnum - 1)(v["params"], x)
            for a, b in zip(jax.tree.leaves(g_t), jax.tree.leaves(g_f)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5)

    def test_eval_mode_uses_running_stats(self):
        tpu_bn, flax_bn = self._pair(use_running_average=True)
        x = jnp.asarray(np.random.RandomState(2).randn(2, 4, 4, 3),
                        jnp.float32)
        v = flax_bn.init(jax.random.PRNGKey(0), x)
        v["batch_stats"]["mean"] = jnp.asarray([0.5, -1.0, 2.0])
        v["batch_stats"]["var"] = jnp.asarray([1.5, 0.25, 4.0])
        y_t = tpu_bn.apply(v, x)
        y_f = flax_bn.apply(v, x)
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_f),
                                   rtol=1e-5, atol=1e-5)

    def test_sync_bn_pmean_equals_full_batch(self):
        """axis_name statistics across a 2-device pmap must equal the
        full-batch statistics (the reference's sync_batch_norm parity)."""
        from horovod_tpu.models.normalization import TpuBatchNorm

        x = jnp.asarray(np.random.RandomState(3).randn(4, 3, 3, 2),
                        jnp.float32)
        full = TpuBatchNorm(use_running_average=False, momentum=0.9,
                            dtype=jnp.float32)
        v = full.init(jax.random.PRNGKey(0), x)
        y_full, _ = full.apply(v, x, mutable=["batch_stats"])

        sync = TpuBatchNorm(use_running_average=False, momentum=0.9,
                            dtype=jnp.float32, axis_name="dp")
        xs = x.reshape(2, 2, 3, 3, 2)
        y_sync, _ = jax.pmap(
            lambda xx: sync.apply(v, xx, mutable=["batch_stats"]),
            axis_name="dp", devices=jax.devices()[:2])(xs)
        np.testing.assert_allclose(np.asarray(y_sync.reshape(x.shape)),
                                   np.asarray(y_full), rtol=1e-5,
                                   atol=1e-5)

    def test_resnet_loss_trajectory_matches_flax_bn(self):
        """norm_impl='tpu' must track norm_impl='flax' step for step —
        the parity-clean-numerics gate for the MFU work (VERDICT r2 #2)."""
        import optax

        from horovod_tpu.models import ResNet50

        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(4, 32, 32, 3), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 10, (4,)))

        def run(norm_impl):
            model = ResNet50(num_classes=10, dtype=jnp.float32,
                             norm_impl=norm_impl)
            variables = _init(model, x, train=True)
            params, bs = variables["params"], variables["batch_stats"]
            tx = optax.sgd(0.05, momentum=0.9)
            opt = tx.init(params)
            losses = []

            @jax.jit
            def step(params, bs, opt):
                def loss_fn(p, b):
                    logits, mut = model.apply(
                        {"params": p, "batch_stats": b}, x, train=True,
                        mutable=["batch_stats"])
                    l = optax.softmax_cross_entropy_with_integer_labels(
                        logits, labels).mean()
                    return l, mut["batch_stats"]

                (l, bs2), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, bs)
                up, opt2 = tx.update(g, opt, params)
                return optax.apply_updates(params, up), bs2, opt2, l

            for _ in range(3):
                params, bs, opt, l = step(params, bs, opt)
                losses.append(float(l))
            return losses

        np.testing.assert_allclose(run("tpu"), run("flax"), rtol=1e-4)

    def test_resnet_bf16_loss_trajectory_tracks_flax_bn(self):
        """Same trajectory check in bf16 — the production default path
        (the fp32 test would pass even if the bf16 affine application
        regressed). Loose tolerance: the two implementations round at
        different points by design."""
        import optax

        from horovod_tpu.models import ResNet50

        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(4, 32, 32, 3), jnp.bfloat16)
        labels = jnp.asarray(rng.randint(0, 10, (4,)))

        def run(norm_impl):
            model = ResNet50(num_classes=10, dtype=jnp.bfloat16,
                             norm_impl=norm_impl)
            variables = _init(model, x, train=True)
            params, bs = variables["params"], variables["batch_stats"]
            # small lr: a big step overfits 4 samples to ~0 loss in one
            # update, where relative comparison is meaningless
            tx = optax.sgd(0.005, momentum=0.9)
            opt = tx.init(params)

            @jax.jit
            def step(params, bs, opt):
                def loss_fn(p, b):
                    logits, mut = model.apply(
                        {"params": p, "batch_stats": b}, x, train=True,
                        mutable=["batch_stats"])
                    l = optax.softmax_cross_entropy_with_integer_labels(
                        logits, labels).mean()
                    return l, mut["batch_stats"]

                (l, bs2), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, bs)
                up, opt2 = tx.update(g, opt, params)
                return optax.apply_updates(params, up), bs2, opt2, l

            losses = []
            for _ in range(3):
                params, bs, opt, l = step(params, bs, opt)
                losses.append(float(l))
            return losses

        t, f = run("tpu"), run("flax")
        assert all(np.isfinite(t)) and all(np.isfinite(f))
        np.testing.assert_allclose(t, f, rtol=0.05, atol=0.02)


def test_vgg16_and_inception_forward_backward():
    """Benchmark-trio parity (reference docs/benchmarks.rst:13-14 runs
    Inception V3 + VGG-16 + ResNet): both models train a step at reduced
    resolution with finite loss/grads; the canonical param counts at
    native resolution are asserted below (VGG16-BN 138.4M incl. the
    4096-wide FCs; InceptionV3 23.8M)."""
    import optax

    from horovod_tpu.models import InceptionV3, VGG16

    # canonical param counts at native resolution: a silently altered
    # tower width would otherwise keep loss/grads finite while the
    # model is no longer the reference trio's
    def n_params(model, size):
        var = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, size, size, 3), jnp.float32),
                               train=True))
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(var["params"]))

    assert abs(n_params(VGG16(num_classes=1000, dtype=jnp.float32), 224)
               - 138.36e6) < 0.3e6
    assert abs(n_params(InceptionV3(num_classes=1000, dtype=jnp.float32),
                        299) - 23.83e6) < 0.1e6

    rs = np.random.RandomState(0)
    for model, size in [(VGG16(num_classes=10, dtype=jnp.float32), 32),
                        (InceptionV3(num_classes=10, dtype=jnp.float32),
                         299)]:
        x = jnp.asarray(rs.randn(2, size, size, 3), jnp.float32)
        y = jnp.asarray(rs.randint(0, 10, (2,)))
        variables = _init(model, x, train=True)
        params, bstats = variables["params"], variables["batch_stats"]

        def loss_fn(p, bstats, x, y):
            logits, _ = model.apply(
                {"params": p, "batch_stats": bstats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        l, g = jax.jit(jax.value_and_grad(loss_fn))(params, bstats, x, y)
        assert np.isfinite(float(l))
        leaves = jax.tree.leaves(g)
        assert leaves and all(np.all(np.isfinite(np.asarray(p)))
                              for p in leaves)


def test_conv0_space_to_depth_odd_input_raises_clear_error():
    """Odd H/W cannot fold 2x2 pixel blocks; the stem must raise a
    ValueError naming conv0_space_to_depth, not an opaque reshape
    error from deep inside XLA."""
    from horovod_tpu.models.resnet import _SpaceToDepthStem

    stem = _SpaceToDepthStem(features=16, dtype=jnp.float32)
    x = jnp.zeros((1, 33, 32, 3), jnp.float32)
    with pytest.raises(ValueError, match="conv0_space_to_depth.*33x32"):
        stem.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="conv0_space_to_depth"):
        stem.init(jax.random.PRNGKey(0),
                  jnp.zeros((1, 32, 31, 3), jnp.float32))
