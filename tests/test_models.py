"""Model + sharding tests (the reference has no models of its own; these
cover the benchmark/flagship models and the driver entry contract)."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


def test_resnet50_forward_shape():
    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    logits, mutated = model.apply(variables, x, train=True,
                                  mutable=["batch_stats"])
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
    std = ResNet50(num_classes=10, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), x, train=True)
    s2d = ResNet50(num_classes=10, dtype=jnp.float32,
                   conv0_space_to_depth=True).init(
        jax.random.PRNGKey(0), x, train=True)
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
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)


def test_gpt_forward():
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    model = GPT(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 64)


def test_gpt_causality():
    # changing a future token must not affect earlier logits
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    model = GPT(cfg)
    rng = np.random.RandomState(1)
    t1 = rng.randint(0, 64, (1, 8))
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 1) % 64
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(t1))
    l1 = model.apply(params, jnp.asarray(t1))
    l2 = model.apply(params, jnp.asarray(t2))
    np.testing.assert_allclose(np.asarray(l1[0, :-1]),
                               np.asarray(l2[0, :-1]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]))


def test_param_partition_spec():
    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.models.transformer import param_partition_spec

    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    model = GPT(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    specs = param_partition_spec(params)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    by_name = {"/".join(str(getattr(k, "key", k)) for k in path): spec
               for path, spec in flat}
    assert by_name["embedding"] == P("tp", None)
    assert any(s == P(None, "tp", None) for n, s in by_name.items()
               if n.endswith("q/kernel"))
    assert any(s == P("tp", None, None) for n, s in by_name.items()
               if n.endswith("o/kernel"))
    assert any(s == P(None, "tp") for n, s in by_name.items()
               if n.endswith("up/kernel"))
    assert any(s == P("tp", None) for n, s in by_name.items()
               if n.endswith("down/kernel"))
    assert any(s == P() for n, s in by_name.items() if "ln" in n)


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


def test_gpt_flash_attention_matches_einsum_path():
    """use_flash must be a pure performance switch: identical logits and
    gradients (the pallas kernel runs in interpret mode on the CPU
    mesh)."""
    import dataclasses

    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 16)))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)
    model_f = GPT(dataclasses.replace(cfg, use_flash=True))

    def loss(m, p):
        return (m.apply(p, tokens).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.value_and_grad(lambda p: loss(model, p))(params)
    l1, g1 = jax.value_and_grad(lambda p: loss(model_f, p))(params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


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
            variables = model.init(jax.random.PRNGKey(0), x, train=True)
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
            variables = model.init(jax.random.PRNGKey(0), x, train=True)
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


@pytest.mark.parametrize("use_flash", [False, True])
def test_gpt_ring_mesh_matches_plain(use_flash):
    """GPTConfig.ring_mesh swaps GSPMD attention for the explicit ring
    schedule (flash per block when use_flash) — logits and gradients
    must match the plain model."""
    import dataclasses

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS

    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.parallel.mesh import make_parallel_mesh

    mesh = make_parallel_mesh(sp=8)
    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 32)))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)
    cfg_ring = dataclasses.replace(cfg, ring_mesh=mesh,
                                   use_flash=use_flash)
    model_r = GPT(cfg_ring)
    tokens_sp = jax.device_put(tokens,
                               NamedSharding(mesh, PS(None, "sp")))

    def loss(m, p, t):
        return (m.apply(p, t).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.value_and_grad(lambda p: loss(model, p, tokens))(params)
    l1, g1 = jax.value_and_grad(
        lambda p: loss(model_r, p, tokens_sp))(params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def test_gpt_use_flash_auto_resolves_by_sequence_length(monkeypatch):
    """use_flash="auto" (opt-in; the default stays False) picks the
    measured winner per sequence length: einsum below the crossover
    measured in the benchmark's cells and on any length no proper score
    tile divides, the flash kernels elsewhere (at 8192 the einsum path
    crashes the TPU worker, so auto is also a safety rail). Verified by
    instrumenting the kernel entry point."""
    import dataclasses

    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.ops import flash_attention as fa

    calls = []
    real = fa.flash_attention

    def spy(*a, **k):
        calls.append(a[0].shape)
        return real(*a, **k)

    monkeypatch.setattr(fa, "flash_attention", spy)
    # "auto" upgrades only on a TPU backend (on the CPU the kernel runs
    # in interpret mode); fake the backend for the resolver and keep the
    # kernel itself interpreted, both steered from here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    # the resolver: the boundary, a length that takes no proper tile
    assert tr._resolve_flash("auto", fa._AUTO_FROM - 128) is False
    assert tr._resolve_flash("auto", fa._AUTO_FROM) is True
    assert tr._resolve_flash("auto", 4096) is True
    for ragged in (fa._AUTO_FROM + 8, 3000, 4100):
        assert tr._resolve_flash("auto", ragged) is False
    assert tr._resolve_flash(True, 16) is True
    assert tr._resolve_flash(False, 100000) is False
    with pytest.raises(ValueError, match="auto"):
        tr._resolve_flash("einsum", 16)

    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32, max_seq_len=4096,
                    use_flash="auto")
    tokens_short = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (1, 16)))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens_short)
    model.apply(params, tokens_short)
    assert not calls, "auto must use einsum at short sequences"

    # a sequence the einsum path serves and the kernels would refuse or
    # crawl through: traced only (shapes decide, nothing runs)
    at = lambda n: jax.eval_shape(
        model.apply, params, jax.ShapeDtypeStruct((1, n), jnp.int32))
    at(fa._AUTO_FROM + 4)
    assert not calls, "auto must use einsum where no proper tile divides"
    at(fa._AUTO_FROM)
    assert calls == [(1, fa._AUTO_FROM, 2, 16)], calls


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
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        params, bstats = variables["params"], variables["batch_stats"]

        def loss_fn(p):
            logits, _ = model.apply(
                {"params": p, "batch_stats": bstats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        l, g = jax.value_and_grad(loss_fn)(params)
        assert np.isfinite(float(l))
        leaves = jax.tree.leaves(g)
        assert leaves and all(np.all(np.isfinite(np.asarray(p)))
                              for p in leaves)


def test_gpt_gqa_all_attention_paths_agree():
    """n_kv_heads (GQA/MQA, LLaMA-2 lineage): einsum, flash, and
    ring-mesh paths must produce identical logits/grads for the same
    params; K/V projections shrink to n_kv_heads."""
    import dataclasses

    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                    n_kv_heads=2, d_ff=64, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 16)))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)

    # K/V kernels carry n_kv_heads
    att0 = params["params"]["block_0"]["attn"]
    assert att0["q"]["kernel"].shape == (32, 4, 8)
    assert att0["k"]["kernel"].shape == (32, 2, 8)
    assert att0["v"]["kernel"].shape == (32, 2, 8)

    def loss(m, p):
        return (m.apply(p, tokens).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.value_and_grad(lambda p: loss(model, p))(params)
    model_f = GPT(dataclasses.replace(cfg, use_flash=True))
    l1, g1 = jax.value_and_grad(lambda p: loss(model_f, p))(params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)

    # MQA (n_kv_heads=1) also runs
    cfg_mqa = dataclasses.replace(cfg, n_kv_heads=1)
    m2 = GPT(cfg_mqa)
    p2 = m2.init(jax.random.PRNGKey(0), tokens)
    assert np.isfinite(float(loss(m2, p2)))

    with pytest.raises(ValueError, match="divide"):
        GPT(dataclasses.replace(cfg, n_kv_heads=3)).init(
            jax.random.PRNGKey(0), tokens)


def test_gpt_gqa_ring_mesh_matches_plain():
    """GQA composes with ring-attention sequence parallelism (K/V
    broadcast before the ring; logits match the non-ring model)."""
    import dataclasses

    from jax.sharding import Mesh

    from horovod_tpu.models import GPT, GPTConfig

    devs = np.array(jax.devices()[:4]).reshape(1, 4)
    mesh = Mesh(devs, ("dp", "sp"))
    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=4,
                    n_kv_heads=2, d_ff=64, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 64, (2, 32)))
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)
    base = model.apply(params, tokens)

    ring = GPT(dataclasses.replace(cfg, ring_mesh=mesh))
    out = ring.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def test_param_partition_spec_gqa_tp_fallback():
    """Round-4 review pin: with n_kv_heads < tp the K/V head axis is not
    divisible over the tp mesh axis — the spec must fall back to
    REPLICATED K/V (Megatron MQA layout) instead of emitting a sharding
    GSPMD rejects. Q keeps its tp sharding either way."""
    from horovod_tpu.models import GPT, GPTConfig
    from horovod_tpu.models.transformer import param_partition_spec

    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=8,
                    n_kv_heads=2, d_ff=64, dtype=jnp.float32)
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    att = params["block_0"]["attn"]

    specs4 = param_partition_spec(params, tp_size=4)
    s_att4 = specs4["block_0"]["attn"]
    assert s_att4["q"]["kernel"] == P(None, "tp", None)
    assert s_att4["k"]["kernel"] == P()       # 2 kv heads % 4 -> replicate
    assert s_att4["v"]["kernel"] == P()

    specs2 = param_partition_spec(params, tp_size=2)
    s_att2 = specs2["block_0"]["attn"]
    assert s_att2["k"]["kernel"] == P(None, "tp", None)  # divisible: shard

    # no tp_size: pre-GQA behavior (assumes divisibility)
    specs = param_partition_spec(params)
    assert specs["block_0"]["attn"]["k"]["kernel"] == P(None, "tp", None)
    del att


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


@pytest.mark.parametrize("remat", [False, True])
def test_gpt_gradient_program_names_its_regions(remat):
    # chipbench/regions.py splits a step's time by these names: flax names
    # the blocks, GPT names what flax does not (the embedding lookup, the
    # vocabulary projection), and nn.remat marks what runs again.
    import re

    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                    d_ff=64, dtype=jnp.float32, remat=remat,
                    use_flash=False)
    model = GPT(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    grad = jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum()))
    names = set(re.findall(r'op_name="([^"]*)"',
                           grad.lower(params).compile().as_text()))

    def holding(*parts):
        return [n for n in names if all(p in n for p in parts)]

    for scope in ("/embed/", "/lm_head/"):
        assert holding("jvp(", scope) and holding("transpose(jvp(", scope)
    assert holding("transpose(jvp(", "/block_1/mlp/")
    assert bool(holding("rematted_computation", "/block_1/")) == remat


# ---- the sparse decoder (OLMoE's block) against its plain reference

_SPARSE = {"num_experts_per_tok": 8, "rope_theta": 10000.0,
           "rms_norm_eps": 1e-5, "router_aux_loss_coef": 0.01,
           "router_z_loss_coef": 0.001}


def _sparse_model(remat):
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                    d_ff=8, dtype=jnp.float32, remat=remat, use_flash=False,
                    n_experts=64, experts_per_token=8, qk_norm=True,
                    tie_embeddings=False, norm_eps=1e-5)
    model = GPT(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    params = model.init(jax.random.key(0), tokens)["params"]
    # at their 0.02 the experts and the router barely move the loss
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 20.0 if "moe" in str(path) else w, params)
    return model, params, tokens


def _sparse_loss(model, params, tokens):
    import optax

    logits, aux = model.apply({"params": params}, tokens, return_aux=True)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (ce + _SPARSE["router_aux_loss_coef"] * aux["load_balance"]
            + _SPARSE["router_z_loss_coef"] * aux["router_z"])


@pytest.mark.parametrize("remat", [False, True])
def test_sparse_gpt_matches_reference(remat):
    """Two layers of 64 experts, 8 a token, q and k normalised over their
    whole width, an untied head: loss and the gradient of every leaf
    against chipbench/reference/olmoe.py, to float32's summation order;
    remat changes nothing."""
    from chipbench.reference import olmoe as reference

    model, params, tokens = _sparse_model(remat)
    assert {"lm_head", "embedding"} <= set(params)
    assert set(params["block_0"]) == {"ln1", "attn", "ln2", "moe"}
    assert set(params["block_0"]["attn"]) == {"q", "k", "v", "o", "q_norm",
                                              "k_norm"}
    assert params["block_0"]["attn"]["q_norm"]["scale"].shape == (32,)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: _sparse_loss(model, p, tokens)))(params)
    (want, _), want_grads = reference.loss_and_grad(params, tokens, _SPARSE)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, want_flat, strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 1e-5, (jax.tree_util.keystr(path), err)
    plain, _, _ = _sparse_model(not remat)
    assert float(_sparse_loss(plain, params, tokens)) == pytest.approx(
        float(got), rel=1e-6)
    # the hidden states and the head a memory-bounded loss multiplies
    hidden = model.apply({"params": params}, tokens, return_hidden=True)
    np.testing.assert_allclose(
        np.asarray(hidden @ params["lm_head"].T),
        np.asarray(model.apply({"params": params}, tokens)), rtol=1e-5,
        atol=1e-5)


def test_dense_gpt_is_the_parents():
    """What the gpt2-large cells build: the parameter tree of the commit
    before the sparse fields, and a step that carries none of the new
    scopes or leaves."""
    import re

    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                    d_ff=128, max_seq_len=8, dtype=jnp.bfloat16, remat=True,
                    use_flash="auto")
    model = GPT(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    shapes = {jax.tree_util.keystr(path): leaf.shape for path, leaf
              in jax.tree_util.tree_leaves_with_path(params)}
    block = lambda i: {
        f"['block_{i}']['ln1']['scale']": (32,),
        f"['block_{i}']['ln2']['scale']": (32,),
        f"['block_{i}']['attn']['q']['kernel']": (32, 4, 8),
        f"['block_{i}']['attn']['k']['kernel']": (32, 4, 8),
        f"['block_{i}']['attn']['v']['kernel']": (32, 4, 8),
        f"['block_{i}']['attn']['o']['kernel']": (4, 8, 32),
        f"['block_{i}']['mlp']['up']['kernel']": (32, 128),
        f"['block_{i}']['mlp']['down']['kernel']": (128, 32)}
    assert shapes == {"['embedding']": (64, 32), "['ln_f']['scale']": (32,),
                      **block(0), **block(1)}
    out, aux = model.apply({"params": params}, tokens, return_aux=True)
    assert aux == {} and out.shape == (2, 8, 64)
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum())).lower(
            params).as_text(debug_info=True)))
    assert any("/block_1/mlp/" in n for n in names)
    for new in ("moe", "q_norm", "k_norm", "ssm", "/norm/"):
        assert not [n for n in names if new in n], new
    assert set(model.init(jax.random.key(0), tokens)) == {"params"}


def test_param_partition_spec_of_a_sparse_model():
    from horovod_tpu.models.transformer import param_partition_spec

    _, params, _ = _sparse_model(False)
    specs = param_partition_spec(params, ep_axis="ep")
    moe = specs["block_1"]["moe"]
    assert moe["gate"] == moe["up"] == P("ep", None, "tp")
    assert moe["down"] == P("ep", "tp", None) and moe["router"] == P()
    assert specs["lm_head"] == specs["embedding"] == P("tp", None)
    assert specs["block_0"]["attn"]["q_norm"]["scale"] == P()
    assert specs["block_0"]["attn"]["q"]["kernel"] == P(None, "tp", None)
    # without an ep axis the expert axis is not sharded
    assert param_partition_spec(params)["block_0"]["moe"]["gate"] == P(
        None, None, "tp")


@pytest.mark.parametrize("field, value, new_leaves", [
    ("qk_norm", True, {"q_norm", "k_norm"}),
    ("tie_embeddings", False, {"lm_head"}),
    ("norm_eps", 1e-2, set()),
    ("rotary", False, set()),
    ("mlp_act", "relu2", set()),
])
def test_gpt_config_field_changes_its_part_only(field, value, new_leaves):
    """Each field OLMoE's block needed: the leaves it adds, and logits
    that differ from the default model's on the same parameters."""
    import dataclasses

    from horovod_tpu.models import GPT, GPTConfig

    base = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                     d_ff=64, dtype=jnp.float32, use_flash=False)
    cfg = dataclasses.replace(base, **{field: value})
    tokens = jax.random.randint(jax.random.key(2), (1, 12), 0, 64)
    params = GPT(cfg).init(jax.random.key(0), tokens)["params"]
    base_params = GPT(base).init(jax.random.key(0), tokens)["params"]
    names = lambda tree: {str(getattr(k, "key", k)) for path, _ in
                          jax.tree_util.tree_leaves_with_path(tree)
                          for k in path}
    assert names(params) - names(base_params) == new_leaves
    got = GPT(cfg).apply({"params": params}, tokens)
    want = GPT(base).apply({"params": base_params}, tokens)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) > 1e-4


def test_sparse_gpt_is_the_parents():
    """What the olmoe-1b-7b cell builds: the parameter tree of the commit
    before the hybrid fields (three stacks over every expert, no buffer,
    no latent or shared leaf), both auxiliary losses, and a step that
    carries the four scopes it had and none of the new ones."""
    import re

    model, params, tokens = _sparse_model(remat=True)
    assert set(model.init(jax.random.key(0), tokens)) == {"params"}
    assert {k: v.shape for k, v in params["block_1"]["moe"].items()} == {
        "router": (32, 64), "gate": (64, 32, 8), "up": (64, 32, 8),
        "down": (64, 8, 32)}
    assert set(params["block_1"]) == {"ln1", "attn", "ln2", "moe"}
    _, aux = model.apply({"params": params}, tokens, return_aux=True)
    assert set(aux) == {"load_balance", "router_z"}
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: _sparse_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)))
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine"):
        assert [n for n in names if f"/{scope}/" in n], scope
    for new in ("ssm_", "moe_latent", "moe_shared", "/norm/"):
        assert not [n for n in names if new in n], new


# ---- the hybrid decoder (Nemotron-H's layers) against its plain reference

_HYBRID = {"norm_eps": 1e-5, "ssm_state_size": 8, "mamba_head_dim": 4,
           "num_experts_per_tok": 3, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "experts_held_first": 4}
_HYBRID_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                  "ssm_out_proj", "moe_latent", "moe_shared")


def _hybrid_model(remat=False, pattern="*EMEM", **changes):
    """A share of a small hybrid: 2 of 8 query heads on 1 of 2 key-value
    heads, 4 of 8 Mamba-2 heads in 1 of 2 groups, experts 4 to 7 of 16."""
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=8, n_kv_heads=2, heads_held=(4, 2), rotary=False,
        d_ff=24, dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-5, mlp_act="relu2", ssm_heads=8,
        ssm_head_dim=4, ssm_groups=2, ssm_state=8, ssm_heads_held=(4, 4),
        n_experts=16, experts_per_token=3, moe_score="sigmoid",
        moe_route_scale=2.5, moe_expert_act="relu2", moe_latent=16,
        moe_shared_ff=40, experts_held=(4, 4))
    cfg = dataclasses.replace(cfg, **changes)
    model = GPT(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)
    variables = model.init(jax.random.key(0), tokens)
    # at their 0.02 the experts and the router barely move the loss
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 10.0 if "moe" in str(path) else w,
        variables["params"])
    return model, params, variables.get("buffers", {}), tokens


def _hybrid_loss(model, params, buffers, tokens):
    import optax

    logits = model.apply({"params": params, "buffers": buffers}, tokens)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()


@pytest.mark.parametrize("remat", [False, True])
def test_hybrid_gpt_matches_reference(remat):
    """One mixer a layer in the pattern's order, all three kinds, each a
    chip's share: the tree, the loss and the gradient of every leaf
    against chipbench/reference/nemotron_h.py, to float32's summation
    order; remat changes nothing; the logits are the reference's."""
    from chipbench.reference import nemotron_h as reference

    model, params, buffers, tokens = _hybrid_model(remat)
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(5)]
    assert kinds == [{"attn"}, {"moe"}, {"ssm"}, {"moe"}, {"ssm"}]
    assert params["block_0"]["attn"]["q"]["kernel"].shape == (32, 2, 4)
    assert params["block_0"]["attn"]["k"]["kernel"].shape == (32, 1, 4)
    assert params["block_1"]["moe"]["up"].shape == (4, 16, 24)
    assert params["block_2"]["ssm"]["in_proj"].shape == (32, 2 * 16 + 16 + 4)
    assert set(buffers) == {"block_1", "block_3"}
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: _hybrid_loss(model, p, buffers, tokens)))(params)
    (want, routing), want_grads = reference.loss_and_grad(
        params, buffers, tokens, _HYBRID)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 2 and routing[0]["own"].shape == (40, 3)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, want_flat, strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert err <= 2e-5, (jax.tree_util.keystr(path), err)
    plain, _, _, _ = _hybrid_model(not remat)
    assert float(_hybrid_loss(plain, params, buffers, tokens)) == \
        pytest.approx(float(got), rel=1e-6)
    # no positional term in the attention: with the Mamba-2 layers' and
    # the causal mask's order taken away a permutation of the positions
    # permutes the logits
    attention_only, only_params, _, _ = _hybrid_model(pattern="*")
    apply = lambda t: attention_only.apply({"params": only_params}, t)
    np.testing.assert_allclose(np.asarray(apply(tokens)[:, -1]),
                               np.asarray(apply(tokens.at[:, :-1].set(
                                   tokens[:, -2::-1]))[:, -1]),
                               rtol=1e-4, atol=1e-5)


def test_hybrid_gradient_program_names_its_scopes_and_scatters_no_row():
    """The seven scopes the benchmark's readers look for are in the
    lowered step of a hybrid, forward and backward, beside the expert
    layer's four; and the only scatters the layers put in the compiled
    gradient program are the grouped products' bookkeeping and a held
    expert layer's sum of a round's rows by token (the embedding's and
    this test's own loss's are outside the blocks)."""
    import re

    model, params, buffers, tokens = _hybrid_model(remat=True)
    grad = jax.jit(jax.grad(
        lambda p: _hybrid_loss(model, p, buffers, tokens)))
    # the names as the compiled program carries them, where a device trace
    # reads them: a held expert layer's round is lowered once and called,
    # and its callers' names stand before its own only from HLO on
    compiled = grad.lower(params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', compiled))
    for scope in _HYBRID_SCOPES + ("moe_route", "moe_dispatch",
                                   "moe_experts", "moe_combine"):
        assert [n for n in names if f"/{scope}/" in n and "jvp(" in n
                and "transpose(" not in n], scope
        assert [n for n in names if f"/{scope}/" in n
                and "transpose(jvp(" in n], scope
    assert [n for n in names if "rematted_computation" in n
            and "/ssm_scan/" in n]
    for line in compiled.splitlines():
        if " scatter(" in line:
            name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert ("/jit(gmm)/" in name or "/jit(tgmm)/" in name
                    or "/moe_dispatch/" in name or "/moe_combine/" in name
                    or "/block_" not in name), name


@pytest.mark.parametrize("remat", [False, True])
def test_recomputed_block_does_not_choose_its_experts_again(remat):
    """What a held expert layer's router chose has no gradient, and
    ``models.GPT`` keeps it under ``remat`` (``moe.HELD_CHOICE``: a bit a
    token and expert, and the slots' order): the gradient program of a
    hybrid with two expert layers holds ``top_k`` twice and sorts the
    ``T x count`` slots twice, once a layer, with ``remat`` as without,
    where a recomputed block would choose and sort a second time (the
    scores it chose from are made again: their gradient needs them)."""
    import re

    model, params, buffers, tokens = _hybrid_model(remat=remat)
    text = jax.jit(jax.grad(lambda p: _hybrid_loss(
        model, p, buffers, tokens))).lower(params).as_text()
    slots = tokens.size * 4
    assert len(re.findall(r"chlo\.top_k", text)) == 2
    # (one lowered ``argsort`` that both layers call)
    assert len(re.findall(
        rf"call @argsort\w*\(.*\(tensor<{slots}xi32>\)", text)) == 2


def _take_attention_heads(p, first, count, group):
    kv = slice(first // group, max(first // group + 1,
                                   (first + count) // group))
    return {"q": {"kernel": p["q"]["kernel"][:, first:first + count]},
            "k": {"kernel": p["k"]["kernel"][:, kv]},
            "v": {"kernel": p["v"]["kernel"][:, kv]},
            "o": {"kernel": p["o"]["kernel"][first:first + count]}}


@pytest.mark.parametrize("count", [2, 4, 8], ids=[
    "part-of-a-group", "a-whole-group", "every-head"])
def test_the_shares_of_the_attention_heads_add_up(count):
    """8 query heads over 2 key-value heads, divided ``8 / count`` ways:
    each chip builds its heads' slices of q and o and the key-value head
    they read, and the shares' outputs summed are the uncut reference's
    attention (the out-projection is linear)."""
    from chipbench.reference import nemotron_h as reference
    from horovod_tpu.models import GPTConfig
    from horovod_tpu.models.transformer import Attention

    base = GPTConfig(d_model=32, n_heads=8, n_kv_heads=2, rotary=False,
                     dtype=jnp.float32, use_flash=False)
    x = jax.random.normal(jax.random.key(0), (2, 12, 32))
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12))
    whole = Attention(base).init(jax.random.key(1), x, positions)["params"]
    want = jax.lax.map(lambda one: reference.attention(one, whole), x)
    total = 0.0
    for first in range(0, 8, count):
        cfg = dataclasses.replace(base, heads_held=(first, count))
        mine = _take_attention_heads(whole, first, count, 4)
        shapes = jax.eval_shape(Attention(cfg).init, jax.random.key(1), x,
                                positions)["params"]
        assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
            lambda a: a.shape, jax.tree.map(lambda a: a, dict(shapes)))
        total = total + Attention(cfg).apply({"params": mine}, x, positions)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("changes, match", [
    ({"layer_pattern": "*EM"}, "names 3 layers"),
    ({"layer_pattern": "*EM?M"}, "layer_pattern holds"),
    ({"heads_held": (1, 2)}, "heads held"),
    ({"heads_held": (0, 3)}, "heads held"),
    ({"heads_held": (6, 4)}, "heads held"),
    ({"ssm_heads_held": (2, 4)}, "whole groups"),
    ({"experts_held": (14, 4)}, "experts held"),
])
def test_hybrid_config_is_refused_by_name(changes, match):
    with pytest.raises(ValueError, match=match):
        _hybrid_model(**changes)


def test_dense_mlp_layer_of_a_pattern_and_param_partition_spec():
    """"-" is the dense MLP (here relu2) behind its one norm; and every
    new leaf has its PartitionSpec: Mamba-2's per-head vectors and
    out-projection rows over tp, the expert stacks over ep, the rest
    replicated."""
    from horovod_tpu.models.transformer import param_partition_spec

    _, params, _, _ = _hybrid_model(pattern="-EM*")
    assert set(params["block_0"]) == {"norm", "mlp"}
    specs = param_partition_spec(params, ep_axis="ep")
    ssm, moe = specs["block_2"]["ssm"], specs["block_1"]["moe"]
    assert ssm["A_log"] == ssm["dt_bias"] == ssm["D_skip"] == P("tp")
    assert ssm["norm_scale"] == P("tp") and ssm["out_proj"] == P("tp", None)
    assert ssm["in_proj"] == ssm["conv_kernel"] == ssm["conv_bias"] == P()
    assert moe["up"] == P("ep", None, "tp") and moe["down"] == P(
        "ep", "tp", None)
    for name in ("router", "latent_in", "latent_out", "shared_up",
                 "shared_down"):
        assert moe[name] == P(), name
    assert specs["block_0"]["norm"]["scale"] == P()
    assert specs["block_3"]["attn"]["o"]["kernel"] == P("tp", None, None)


def test_ssm_and_held_counters_show_on_metrics():
    """Both trace-time counters are on ``/metrics`` once a hybrid has
    been traced: the state-space layers by heads, state and chunk, the
    expert layers with what they hold."""
    from horovod_tpu import metrics

    model, params, buffers, tokens = _hybrid_model()
    jax.jit(lambda p: _hybrid_loss(model, p, buffers, tokens)).lower(params)
    text = metrics.prometheus_text()
    assert re.search(r'hvt_ssm_layers_traced_total\{[^}]*chunk="20"[^}]*\}',
                     text), text[-2000:]
    assert 'heads="4"' in text and 'state="8"' in text
    assert re.search(r'hvt_moe_layers_traced_total\{[^}]*held="4"[^}]*\}',
                     text)


# ---- Qwen3-Next's layers (Gated DeltaNet, gated attention with per-head
# norms and a partial rotary, a renormalised softmax router with a gated
# shared expert) against their plain reference

_QWEN = {"rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
         "linear_num_value_heads": 4, "linear_key_head_dim": 8,
         "linear_value_head_dim": 8, "head_dim": 16,
         "partial_rotary_factor": 0.25, "rope_theta": 1e7,
         "num_experts_per_tok": 3, "norm_topk_prob": True,
         "experts_held_first": 4}
_QWEN_SCOPES = ("gdn_in_proj", "gdn_conv", "gdn_rule", "gdn_gate_norm",
                "gdn_out_proj", "moe_shared")


def _qwen_model(remat=False, pattern="GEGE*E", **changes):
    """A share of a small Qwen3-Next: heads of 16 where d_model / n_heads
    is 8, 4 query heads on 2 key-value heads, 2 key heads serving 4 value
    heads in the Gated DeltaNet mixers, experts 4 to 7 of 16."""
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(
        vocab_size=64, n_layers=len(pattern), layer_pattern=pattern,
        d_model=32, n_heads=4, n_kv_heads=2, head_dim=16, head_norm=True,
        attn_gate=True, rotary_base=1e7, rotary_fraction=0.25, d_ff=16,
        dtype=jnp.float32, remat=remat, use_flash=False,
        tie_embeddings=False, norm_eps=1e-6, norm_unit_offset=True,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
        n_experts=16, experts_per_token=3, moe_renormalise=True,
        moe_shared_gate=True, moe_shared_ff=24, experts_held=(4, 4))
    cfg = dataclasses.replace(cfg, **changes)
    model = GPT(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)
    params = model.init(jax.random.key(0), tokens)["params"]
    # off their initial values: at 0.02 the layers barely move the loss,
    # and a norm's weight of 0 or a scale of 1 hides which of the two it is
    keys = iter(jax.random.split(jax.random.key(2),
                                 len(jax.tree.leaves(params))))
    params = jax.tree.map(
        lambda w: w + 0.2 * jax.random.normal(next(keys), w.shape), params)
    return model, params, tokens


def _qwen_loss(model, params, tokens, sow=False):
    import optax

    logits, sown = model.apply({"params": params}, tokens,
                               mutable=["intermediates"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], tokens[:, 1:]).mean()
    return (loss, sown["intermediates"]) if sow else loss


@pytest.mark.parametrize("remat", [False, True])
def test_qwen3_next_gpt_matches_reference(remat):
    """All three kinds of layer in the source's order, the experts a
    chip's share: the tree, the loss and the gradient of every leaf
    against chipbench/reference/qwen3_next.py given the program's choice
    of experts, to float32's summation order; remat changes nothing."""
    from chipbench.reference import qwen3_next as reference

    model, params, tokens = _qwen_model(remat)
    kinds = [set(params[f"block_{i}"]) - {"norm"} for i in range(6)]
    assert kinds == [{"gdn"}, {"moe"}, {"gdn"}, {"moe"}, {"attn"}, {"moe"}]
    attn, experts = params["block_4"]["attn"], params["block_1"]["moe"]
    assert attn["q"]["kernel"].shape == (32, 4, 32)     # [query | gate]
    assert attn["k"]["kernel"].shape == (32, 2, 16)
    assert attn["o"]["kernel"].shape == (4, 16, 32)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert experts["up"].shape == (4, 32, 16)
    assert experts["router"].shape == (32, 16)
    assert experts["shared_expert_gate"].shape == (32, 1)
    assert params["block_0"]["gdn"]["in_proj_qkvz"].shape == (32, 96)
    (got, sown), grads = jax.jit(jax.value_and_grad(
        lambda p: _qwen_loss(model, p, tokens, sow=True), has_aux=True))(
            params)
    chosen = [sown[f"block_{i}"]["moe"]["experts"][0] for i in (1, 3, 5)]
    (want, routing), want_grads = reference.loss_and_grad(
        params, tokens, _QWEN, chosen)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert len(routing) == 3 and routing[0]["own"].shape == (40, 3)
    for mine, theirs in zip(chosen, routing):
        np.testing.assert_array_equal(np.sort(np.asarray(mine), -1),
                                      np.sort(np.asarray(theirs["own"]), -1))
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (grads, want_grads))
    for (path, g), (_, w) in zip(flat, want_flat, strict=True):
        err = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        # A_log and dt_bias: see tests/test_gdn.py's DECAY_REL
        bound = 2e-4 if path[-1].key in ("A_log", "dt_bias") else 2e-5
        assert err <= bound, (jax.tree_util.keystr(path), err)
    plain, _, _ = _qwen_model(not remat)
    assert float(_qwen_loss(plain, params, tokens)) == pytest.approx(
        float(got), rel=1e-6)


def test_gated_attention_matches_the_formula():
    """The attention layer alone against the reference's: a head of 16
    where d_model / n_heads is 8, the (1 + w) norm a head on q and k, the
    rotary over the first quarter of a head at base 1e7, the output times
    sigmoid(gate); einsum path, float32."""
    from chipbench.reference import qwen3_next as reference
    from horovod_tpu.models.transformer import Attention

    model, _, _ = _qwen_model()
    layer = Attention(model.cfg)
    x = jax.random.normal(jax.random.key(3), (2, 24, 32))
    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
    params = layer.init(jax.random.key(4), x, positions)["params"]
    keys = iter(jax.random.split(jax.random.key(5), 8))
    params = jax.tree.map(
        lambda w: w + 0.3 * jax.random.normal(next(keys), w.shape), params)
    got = layer.apply({"params": params}, x, positions)
    want = jax.vmap(lambda h: reference.attention(h, params, _QWEN))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # positions past the rotated quarter carry no position: with q's and
    # k's first four channels zeroed the rotary changes nothing
    from horovod_tpu.models.transformer import _rotary

    t = jax.random.normal(jax.random.key(6), (2, 24, 4, 16))
    turned = _rotary(t, positions, 1e7, 4)
    np.testing.assert_array_equal(np.asarray(turned[..., 4:]),
                                  np.asarray(t[..., 4:]))
    assert float(jnp.max(jnp.abs(turned[:, 1:, :, :4] - t[:, 1:, :, :4]))) > .1
    np.testing.assert_allclose(
        np.asarray(_rotary(t, positions, 10000.0, None)),
        np.asarray(_rotary(t, positions)), rtol=0, atol=0)


def test_unit_offset_norm_is_one_plus_its_weight():
    from horovod_tpu.models.transformer import RMSNorm

    x = jax.random.normal(jax.random.key(0), (3, 8))
    plain, offset = RMSNorm(1e-6), RMSNorm(1e-6, unit_offset=True)
    p_plain = plain.init(jax.random.key(1), x)["params"]
    p_offset = offset.init(jax.random.key(1), x)["params"]
    np.testing.assert_array_equal(np.asarray(p_plain["scale"]), np.ones(8))
    np.testing.assert_array_equal(np.asarray(p_offset["scale"]), np.zeros(8))
    np.testing.assert_allclose(
        np.asarray(offset.apply({"params": p_offset}, x)),
        np.asarray(plain.apply({"params": p_plain}, x)), rtol=1e-6)
    w = jax.random.normal(jax.random.key(2), (8,))
    np.testing.assert_allclose(
        np.asarray(offset.apply({"params": {"scale": w}}, x)),
        np.asarray(plain.apply({"params": {"scale": 1.0 + w}}, x)),
        rtol=1e-6)


@pytest.mark.parametrize("field, value, new_leaves", [
    ("head_dim", 32, set()),
    ("head_norm", True, {"q_norm", "k_norm"}),
    ("rotary_base", 1e7, set()),
    ("rotary_fraction", 0.25, set()),
    ("attn_gate", True, set()),
])
def test_qwen_attention_field_changes_its_part_only(field, value, new_leaves):
    """Each field Qwen3-Next's attention needed defaults to the layer as
    it was: the leaves it adds, and logits that differ from the default
    model's once the parameters are off their initial values."""
    from horovod_tpu.models import GPT, GPTConfig

    base = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                     d_ff=64, dtype=jnp.float32, use_flash=False)
    cfg = dataclasses.replace(base, **{field: value})
    tokens = jax.random.randint(jax.random.key(2), (1, 12), 0, 64)
    params = GPT(cfg).init(jax.random.key(0), tokens)["params"]
    base_params = GPT(base).init(jax.random.key(0), tokens)["params"]
    names = lambda tree: {str(getattr(k, "key", k)) for path, _ in
                          jax.tree_util.tree_leaves_with_path(tree)
                          for k in path}
    assert names(params) - names(base_params) == new_leaves
    got = GPT(cfg).apply({"params": params}, tokens)
    want = GPT(base).apply({"params": base_params}, tokens)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) > 1e-4


def test_qwen3_next_gradient_program_names_its_scopes():
    """The scopes the benchmark's readers look for are in the lowered
    step, forward and backward; the mixers count themselves; and every
    new leaf has its PartitionSpec."""
    from horovod_tpu import metrics
    from horovod_tpu.models.transformer import param_partition_spec

    model, params, tokens = _qwen_model(remat=True)
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: _qwen_loss(model, p, tokens))).lower(params).as_text(
            debug_info=True)))
    for scope in _QWEN_SCOPES:
        found = [n for n in names if f"/{scope}/" in n]
        assert [n for n in found if "transpose" in n], scope
        assert [n for n in found if "transpose" not in n], scope
    assert [n for n in names if "/moe_route/" in n]
    counted = metrics.registry().get("hvt_gdn_layers_traced_total")
    assert counted.labels(value_heads="4", key_dim="8", value_dim="8",
                          chunk="20").value >= 2
    specs = param_partition_spec(params, ep_axis="ep")
    assert specs["block_0"]["gdn"] == {
        "in_proj_qkvz": P(), "in_proj_ba": P(), "conv_kernel": P(),
        "dt_bias": P("tp"), "A_log": P("tp"), "norm_scale": P(),
        "out_proj": P("tp", None)}
    attn = specs["block_4"]["attn"]
    assert attn["q"]["kernel"] == P(None, "tp", None)
    assert attn["o"]["kernel"] == P("tp", None, None)
    assert attn["q_norm"]["scale"] == P() and attn["k_norm"]["scale"] == P()
    assert specs["block_1"]["moe"]["shared_expert_gate"] == P()
    assert specs["block_1"]["moe"]["up"] == P("ep", None, "tp")


def test_pattern_error_names_the_new_letter():
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=16, n_layers=1, d_model=8, n_heads=2,
                    layer_pattern="Q", dtype=jnp.float32)
    with pytest.raises(ValueError, match=r"'G' \(Gated DeltaNet\)"):
        GPT(cfg).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="one or the other"):
        GPT(dataclasses.replace(cfg, layer_pattern="*", qk_norm=True,
                                head_norm=True)).init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


@functools.cache
def _qwen_sound():
    """The small model with the reference's loss on it, made once."""
    from chipbench.reference import qwen3_next as reference

    model, params, tokens = _qwen_model()
    return model, params, tokens, reference.loss(params, tokens, _QWEN)[0]


# wrong programs: each reads a loss the family's step-loss comparison
# refuses (``LOSS_REL_BOUND``, the bound `chipbench/families/qwen3_next.py`
# holds the measured step to), where the program as it is passes it
@pytest.mark.parametrize("wrong, changes", [
    ("rotary-over-the-whole-head", {"rotary_fraction": 1.0}),
    ("attention-gate-left-out", {"attn_gate": False}),
    ("weights-not-renormalised", {"moe_renormalise": False}),
    ("shared-expert-gate-left-out", {"moe_shared_gate": False}),
    ("norm-without-its-one", {"norm_unit_offset": False}),
    ("norm-over-the-projected-width", {"head_norm": False, "qk_norm": True}),
])
def test_wrong_qwen3_next_models_are_refused(wrong, changes):
    from chipbench import compare
    from chipbench.families import qwen3_next as family

    model, params, tokens, want = _qwen_sound()
    sound = compare.close("loss", float(_qwen_loss(model, params, tokens)),
                          want, family.LOSS_REL_BOUND, floor=1.0)
    assert sound.ok, sound.line()
    other, _, _ = _qwen_model(**changes)
    theirs = other.init(jax.random.key(0), tokens)["params"]

    def fitted(path, leaf):
        """The right model's leaf, cut to the wrong one's shape (the q
        projection without its gate's columns, a norm over the width)."""
        mine = params
        for k in path:
            mine = mine.get(k.key) if isinstance(mine, dict) else None
            if mine is None:
                return leaf
        if mine.shape == leaf.shape:
            return mine
        if path[-2].key == "q":
            return mine[..., :leaf.shape[-1]]
        return jnp.resize(mine, leaf.shape)

    theirs = jax.tree_util.tree_map_with_path(fitted, theirs)
    far = compare.close("loss", float(_qwen_loss(other, theirs, tokens)),
                        want, family.LOSS_REL_BOUND, floor=1.0)
    assert not far.ok, (wrong, far.line())
