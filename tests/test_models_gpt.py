"""The dense GPT, its attention paths (einsum, flash, ring, grouped
queries), its partition specs and the regions its gradient program names."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


def test_gpt_forward():
    from horovod_tpu.models import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=64, n_layers=2, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32)
    model = GPT(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 16)))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    logits = jax.jit(model.apply)(params, tokens)
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
    l1 = jax.jit(model.apply)(params, jnp.asarray(t1))
    l2 = jax.jit(model.apply)(params, jnp.asarray(t2))
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
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
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
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    model_f = GPT(dataclasses.replace(cfg, use_flash=True))

    def loss(m, p):
        return (m.apply(p, tokens).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss(model, p)))(params)
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: loss(model_f, p)))(
        params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


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
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    cfg_ring = dataclasses.replace(cfg, ring_mesh=mesh,
                                   use_flash=use_flash)
    model_r = GPT(cfg_ring)
    tokens_sp = jax.device_put(tokens,
                               NamedSharding(mesh, PS(None, "sp")))

    def loss(m, p, t):
        return (m.apply(p, t).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.jit(jax.value_and_grad(
        lambda p: loss(model, p, tokens)))(params)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: loss(model_r, p, tokens_sp)))(params)
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
    from horovod_tpu.ops import _pallas
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
    monkeypatch.setattr(_pallas, "interpret", lambda: True)
    # the resolver: the boundary, a length that takes no proper tile
    assert fa.resolve_flash("auto", fa._AUTO_FROM - 128) is False
    assert fa.resolve_flash("auto", fa._AUTO_FROM) is True
    assert fa.resolve_flash("auto", 4096) is True
    for ragged in (fa._AUTO_FROM + 8, 3000, 4100):
        assert fa.resolve_flash("auto", ragged) is False
    assert fa.resolve_flash(True, 16) is True
    assert fa.resolve_flash(False, 100000) is False
    with pytest.raises(ValueError, match="auto"):
        fa.resolve_flash("einsum", 16)

    cfg = GPTConfig(vocab_size=64, n_layers=1, d_model=32, n_heads=2,
                    d_ff=64, dtype=jnp.float32, max_seq_len=4096,
                    use_flash="auto")
    tokens_short = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (1, 16)))
    model = GPT(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens_short)
    jax.jit(model.apply)(params, tokens_short)
    assert not calls, "auto must use einsum at short sequences"

    # a sequence the einsum path serves and the kernels would refuse or
    # crawl through: traced only (shapes decide, nothing runs)
    at = lambda n: jax.eval_shape(
        model.apply, params, jax.ShapeDtypeStruct((1, n), jnp.int32))
    at(fa._AUTO_FROM + 4)
    assert not calls, "auto must use einsum where no proper tile divides"
    at(fa._AUTO_FROM)
    assert calls == [(1, fa._AUTO_FROM, 2, 16)], calls


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
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)

    # K/V kernels carry n_kv_heads
    att0 = params["params"]["block_0"]["attn"]
    assert att0["q"]["kernel"].shape == (32, 4, 8)
    assert att0["k"]["kernel"].shape == (32, 2, 8)
    assert att0["v"]["kernel"].shape == (32, 2, 8)

    def loss(m, p):
        return (m.apply(p, tokens).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss(model, p)))(params)
    model_f = GPT(dataclasses.replace(cfg, use_flash=True))
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: loss(model_f, p)))(
        params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)

    # MQA (n_kv_heads=1) also runs
    cfg_mqa = dataclasses.replace(cfg, n_kv_heads=1)
    m2 = GPT(cfg_mqa)
    p2 = jax.jit(m2.init)(jax.random.PRNGKey(0), tokens)
    assert np.isfinite(float(jax.jit(lambda p: loss(m2, p))(p2)))

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
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    base = jax.jit(model.apply)(params, tokens)

    ring = GPT(dataclasses.replace(cfg, ring_mesh=mesh))
    out = jax.jit(ring.apply)(params, tokens)
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
    params = jax.eval_shape(GPT(cfg).init, jax.random.PRNGKey(0),
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
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
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
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
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
    out, aux = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, return_aux=True))(params)
    assert aux == {} and out.shape == (2, 8, 64)
    names = set(re.findall(r'loc\("([^"]*)"', jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, tokens).sum())).lower(
            params).as_text(debug_info=True)))
    assert any("/block_1/mlp/" in n for n in names)
    for new in ("moe", "q_norm", "k_norm", "ssm", "/norm/"):
        assert not [n for n in names if new in n], new
    assert set(jax.eval_shape(model.init, jax.random.key(0), tokens)) == {
        "params"}


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
    params = jax.jit(GPT(cfg).init)(jax.random.key(0), tokens)["params"]
    base_params = jax.jit(GPT(base).init)(
        jax.random.key(0), tokens)["params"]
    names = lambda tree: {str(getattr(k, "key", k)) for path, _ in
                          jax.tree_util.tree_leaves_with_path(tree)
                          for k in path}
    assert names(params) - names(base_params) == new_leaves
    got = jax.jit(GPT(cfg).apply)({"params": params}, tokens)
    want = jax.jit(GPT(base).apply)({"params": base_params}, tokens)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) > 1e-4
