"""Main-path kernels at real width, compiled for a v5e that is described
and not attached (the chip's compiler is installed with jaxlib's TPU
runtime). Interpret-mode tests cannot see what Mosaic refuses — a slice
not aligned to the tiling, a kernel over its VMEM budget — and these can,
at no chip time. A compile that passes here is not a chip run.

The compiles run in the pytest process, one at a time: two processes that
describe the topology at once collide on libtpu's lock file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models import moe, ssm
from horovod_tpu.ops import _pallas
from horovod_tpu.ops import causal_conv as conv
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import gated_delta_rule as gdr
from horovod_tpu.ops import head_norm
from horovod_tpu.ops import kth_largest as kth
from horovod_tpu.ops import rotary as rotary_op
from horovod_tpu.parallel.sequence import ring_attention


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return list(topo.devices)


@pytest.fixture()
def compiled_kernel(monkeypatch):
    """Steer the kernels to their compiled form from the test (every
    family asks ``_pallas.interpret()``, which reads
    ``jax.default_backend()``, the CPU here), with the persistent cache
    off: an entry compiled for a described device is written but cannot be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(_pallas, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _loss(attend):
    def loss(q, k, v):
        return (attend(q, k, v).astype(jnp.float32) ** 2).mean()

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def _qkv(shape, sharding):
    """``(batch, seq, heads, kv_heads, head_dim)`` and, where the values'
    width is not the keys', that width after them."""
    b, s, h, h_kv, d, d_v = (*shape, shape[-1])[:6]
    like = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                              sharding=sharding)
    return like(b, s, h, d), like(b, s, h_kv, d), like(b, s, h_kv, d_v)


# (batch, seq, heads, kv_heads, head_dim[, value_dim]): the shapes a 12 x 768 GPT and
# chip_smoke.py run, the benchmark cells gpt2l-s1024's, gpt2l-s4096's and
# olmoe-s4096's and nemotron3s-s8192's own (four query heads on one
# key-value head at 8192 positions) and qwen3next-s8192's (a head of 256,
# sixteen query heads on two key-value heads) and kanana2-s8192's (latent
# attention: 32 heads, queries and keys of 192 on values of 128, whole and
# as the mixer hands them over since PR 49: 128 a head beside a rotated
# pair of 64 whose key has no head axis), gpt2l-dp4's (four chips' batch under a shard_map
# that checks vma), gpt2-large's heads at 4 x 2048 and at 1 x 8192 (two
# streamed tiles: the backward's dQ accumulator is addressed by a dynamic
# slice and leaves a tile at a time), one grouped-query shape, the 4-chip
# ring, and one sequence whose clipped block Mosaic refuses. No blocks
# are passed: each kernel's score tile is the one derived from the shape,
# and every step is value_and_grad, so the forward and the one backward
# kernel are both compiled
@pytest.mark.parametrize("kind,shape", [
    pytest.param("flash", (8, 1024, 12, 12, 64), id="flash-8x1024"),
    pytest.param("flash", (2, 4096, 12, 12, 64), id="flash-2x4096"),
    pytest.param("flash", (1, 8192, 12, 12, 64), id="flash-1x8192"),
    pytest.param("flash", (8, 1024, 20, 20, 64), id="flash-gpt2l-s1024"),
    pytest.param("flash", (2, 4096, 20, 20, 64), id="flash-gpt2l-s4096"),
    pytest.param("flash", (4, 2048, 20, 20, 64), id="flash-gpt2l-4x2048"),
    pytest.param("flash", (1, 8192, 20, 20, 64), id="flash-gpt2l-1x8192"),
    pytest.param("shard_map", (32, 1024, 20, 20, 64),
                 id="flash-gpt2l-dp4-shard-map"),
    pytest.param("flash", (1, 4096, 32, 8, 128), id="flash-gqa-32-8-128"),
    pytest.param("flash", (2, 4096, 16, 16, 128), id="flash-olmoe-s4096"),
    pytest.param("flash", (2, 8192, 4, 1, 128), id="flash-nemotron3s-s8192"),
    pytest.param("flash", (2, 8192, 16, 2, 256), id="flash-qwen3next-s8192"),
    pytest.param("flash", (2, 8192, 32, 32, 192, 128),
                 id="flash-kanana2-s8192"),
    pytest.param("rotated", (2, 8192, 32, 32, 192, 128),
                 id="flash-kanana2-s8192-rotated-pair"),
    pytest.param("ring", (1, 16384, 12, 12, 64), id="ring-sp4-16384"),
    pytest.param("refused", (1, 100, 2, 2, 64), id="block-not-multiple-of-8"),
])
def test_main_path_kernel_compiles_for_v5e(kind, shape, compiled_kernel,
                                           request):
    if kind == "refused":
        # named at trace time, before Mosaic: no device needed
        with pytest.raises(ValueError, match="multiples of 8"):
            jax.eval_shape(
                lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                *_qkv(shape, None))
        return
    devices = request.getfixturevalue("v5e_devices")
    if kind == "rotated":
        one = SingleDeviceSharding(devices[0])
        (b, seq, h, _, d, _), e = shape, 64
        q_n, k_n, v = _qkv((*shape[:4], d - e, shape[5]), one)
        q_r = jax.ShapeDtypeStruct((b, seq, h, e), jnp.bfloat16, sharding=one)
        k_r = jax.ShapeDtypeStruct((b, seq, e), jnp.bfloat16, sharding=one)
        step = jax.jit(jax.value_and_grad(
            lambda q_n, q_r, k_n, k_r, v: (fa.flash_attention(
                q_n, k_n, v, q_r=q_r, k_r=k_r, causal=True).astype(
                    jnp.float32) ** 2).mean(), argnums=(0, 1, 2, 3, 4)))
        args = (q_n, q_r, k_n, k_r, v)
    elif kind == "flash":
        step = _loss(lambda q, k, v: fa.flash_attention(q, k, v,
                                                        causal=True))
        args = _qkv(shape, SingleDeviceSharding(devices[0]))
    elif kind == "shard_map":
        mesh = Mesh(np.array(devices), ("world",))
        rows = P("world")
        per_chip = _loss(lambda q, k, v: fa.flash_attention(q, k, v,
                                                            causal=True))
        step = jax.jit(jax.shard_map(
            lambda q, k, v: jax.tree.map(
                lambda x: jax.lax.pmean(x, "world"), per_chip(q, k, v)),
            mesh=mesh, in_specs=(rows,) * 3, out_specs=P()))
        args = _qkv(shape, NamedSharding(mesh, rows))
    else:
        mesh = Mesh(np.array(devices), ("sp",))
        step = _loss(lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, causal=True, use_flash=True))
        args = _qkv(shape, NamedSharding(mesh, P(None, "sp", None, None)))
    text = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "hvt_flash_fwd" in text and "hvt_flash_bwd" in text
    if kind == "ring":
        assert "collective-permute" in text


def test_layers_share_one_lowered_kernel(compiled_kernel, v5e_devices):
    """Each kernel is traced and lowered once a program: a two-layer
    ``models.GPT`` training step holds as many ``tpu_custom_call`` sites
    as a one-layer step (the forward's, the recomputed forward's and the
    one backward's), each called once a layer, and two Pallas kernels by
    name. Lowered, not compiled."""
    from horovod_tpu.models import GPT, GPTConfig

    one_chip = SingleDeviceSharding(v5e_devices[0])

    def sites(n_layers):
        model = GPT(GPTConfig(
            vocab_size=512, n_layers=n_layers, d_model=128, n_heads=2,
            d_ff=256, max_seq_len=1024, remat=True, use_flash=True))
        tokens = jax.ShapeDtypeStruct((2, 1024), jnp.int32,
                                      sharding=one_chip)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.key(0), tokens))
        loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()
        text = jax.jit(jax.grad(loss)).lower(params, tokens).as_text()
        kernels = set(re.findall(r"hvt_flash_\w+", text))
        return text.count("tpu_custom_call"), text.count("call @"), kernels

    (one, calls_one, names), (two, calls_two, _) = sites(1), sites(2)
    assert one == two == 3, (one, two)
    assert calls_two > calls_one
    assert names == {"hvt_flash_fwd", "hvt_flash_bwd"}


def test_latent_attention_layers_share_the_kernels_under_their_scope(
        compiled_kernel, v5e_devices):
    """A three-layer ``models.GPT`` of latent-attention mixers at the
    published widths (queries and keys of 128 | 64 on values of 128) holds
    three ``tpu_custom_call`` sites however many layers it has (the
    forward's, the recomputed forward's and the one backward's), the two
    flash kernels by name, and, compiled, each of the nine calls'
    ``op_name`` has the scope ``mla_core`` in it (what
    ``chipbench/layer_metrics/mla_core_roofline.py`` chooses the kernels
    by) and the pass it belongs to (what ``chipbench/regions.py`` splits
    the step by). Since PR 49 the kernels take the query and the key in
    their two parts: nothing 192 wide is under ``mla_core`` in either
    pass, and no activation 192 wide exists at all (no key assembled, no
    query concatenated, no gradient padded back or cut apart)."""
    from horovod_tpu.models import GPT, GPTConfig

    one_chip = SingleDeviceSharding(v5e_devices[0])

    def step(pattern):
        model = GPT(GPTConfig(
            vocab_size=512, n_layers=len(pattern), layer_pattern=pattern,
            d_model=128, n_heads=2, d_ff=256, max_seq_len=1024, remat=True,
            use_flash=True, mla_kv_rank=64, rotary_base=1e6))
        tokens = jax.ShapeDtypeStruct((2, 1024), jnp.int32,
                                      sharding=one_chip)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.key(0), tokens))
        assert params["params"]["block_0"]["mla"]["kv_up"].shape == (
            64, 2, 256)
        loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()
        return jax.jit(jax.grad(loss)).lower(params, tokens)

    one, three = step("L"), step("LLL")
    sites = lambda lowered: lowered.as_text().count(
        "stablehlo.custom_call @tpu_custom_call")
    assert sites(one) == sites(three) == 3, (sites(one), sites(three))
    assert set(re.findall(r"hvt_flash_\w+", three.as_text())) == {
        "hvt_flash_fwd", "hvt_flash_bwd"}
    text = three.compile().as_text()
    # q_n, k_n, v and o a head; q_r a head; the one k_r a position
    assert all(shape in text for shape in (
        "bf16[2,2,1024,128]", "bf16[2,2,1024,64]", "bf16[2,1024,64]"))
    assert not re.search(r"\[2,(1024,2|2,1024),192\]", text)
    core = [line for line in text.splitlines() if "/mla_core/" in line]
    assert core and not [line for line in core if ",192]" in line]
    for opcode in ("concatenate", "pad", "broadcast"):
        assert not [line for line in core
                    if re.search(rf" {opcode}\(", line)
                    and re.search(r"bf16\[2,\d+,\d+,\d+\]", line)], opcode
    # inlined, each call keeps its call site's whole op_name
    names = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    assert len(names) == 9 and all("/mla_core/" in n for n in names), names
    kinds = lambda kernel, inside: [
        n for n in names if f"/{kernel}/" in n and inside(n)]
    assert len(kinds("hvt_flash_fwd", lambda n: "transpose(" not in n)) == 3
    assert len(kinds("hvt_flash_fwd",
                     lambda n: "rematted_computation" in n)) == 3
    assert len(kinds("hvt_flash_bwd", lambda n: "transpose(jvp(" in n
                     and "rematted_computation" not in n)) == 3


def test_expert_layer_compiles_for_v5e(compiled_kernel, v5e_devices):
    """The benchmark cell olmoe-s4096's expert layer at its own sizes
    (2 x 4096 tokens, 64 experts of 1024, 8 a token, bf16), forward and
    backward: the nine grouped products are Pallas calls whose tile fits
    VMEM, and no scatter is over rows or weights: the only ones are the
    products' bookkeeping (which tile belongs to which group)."""
    tokens, d, experts, k = 2 * 4096, 2048, 64, 8
    layer = moe.MoEMlp(experts, 1024, k, dtype=jnp.bfloat16)
    one = SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16, sharding=one)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(layer.init, jax.random.key(0), x)["params"])

    def loss(params, x):
        out, aux = layer.apply({"params": params}, x)
        return (jnp.mean(out.astype(jnp.float32) ** 2)
                + aux["load_balance"] + aux["router_z"])

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 9
    tiles = tokens * k // 512
    for line in text.splitlines():
        if " scatter(" in line:
            shape = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
            assert "," not in shape and int(shape) <= 2 * experts + tiles, line


def _shapes_on(device, init, *args):
    one = SingleDeviceSharding(device)
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    args = place(args)
    return place(dict(jax.eval_shape(init, jax.random.key(0), *args))), args


def test_mamba2_layer_compiles_for_v5e(v5e_devices):
    """The benchmark cell nemotron3s-s8192's Mamba-2 mixer at its own
    sizes (2 x 8192 positions of 4096, 16 of 128 heads of 64 in 1 of 8
    groups, a state of 128, bf16), forward and backward: the chunked scan
    is plain XLA (no Pallas call), its carry one loop each way, and its
    temporaries fit beside a training step (under 2 GiB)."""
    from horovod_tpu.models import ssm

    layer = ssm.Mamba2Mixer(128, 64, 8, 128, held=(0, 16),
                            dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 8192, 4096), jnp.bfloat16)
    variables, (x,) = _shapes_on(v5e_devices[0], layer.init, x)
    assert variables["params"]["in_proj"].shape == (4096, 2 * 1024 + 256 + 16)

    def loss(params, x):
        return jnp.mean(layer.apply({"params": params}, x).astype(
            jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables["params"], x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert len(re.findall(r" while\(", text)) >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30


@pytest.mark.parametrize("k", [22, 10])
def test_kth_largest_kernel_compiles_for_v5e(k, compiled_kernel,
                                             v5e_devices):
    """The routers of nemotron3s-s8192 and qwen3next-s8192: the ``k``-th
    largest of 512 scores for 2 x 8192 tokens, one Pallas call whose
    turned block fits VMEM, no sort beside it."""
    x = jax.ShapeDtypeStruct((2 * 8192, 512), jnp.float32,
                             sharding=SingleDeviceSharding(v5e_devices[0]))
    text = jax.jit(lambda x: kth.kth_largest(x, k)).lower(
        x).compile().as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 1
    assert "hvt_moe_kth" in text and " sort(" not in text


def test_held_expert_layer_compiles_for_v5e(compiled_kernel, v5e_devices,
                                            monkeypatch):
    """The benchmark cell nemotron3s-s8192's expert layer at its own
    sizes (2 x 8192 tokens of 4096, a sigmoid router over 512 experts, 22
    a token, experts 0 to 7 held, relu2 experts of 2688 in a latent of
    1024, a shared expert of 5376, bf16), forward and backward: two
    stacks, so eight grouped products as Pallas calls (two in the forward
    loop over the rounds; in the backward loop two to make a round's
    forward again and four for its pullback), every one over a round of
    16384 rows and none over the 16384 x 8 slots; a round's sums by
    token are a Pallas call each (``ops/sum_by_token.py``: the combine's
    in the forward loop, the dispatch's transpose in the backward one),
    under those two scopes, so the scatters left are the products'
    bookkeeping and the transpose of the gather of a round's weights. The
    eleventh Pallas call is the router's ``k``-th score under
    ``moe_route`` (steered here as a TPU would choose): no ``top_k`` is
    left in the program, nothing of ``T x k x E`` elements, and one sort
    beside those of a round's ``T`` tokens, of the ``T x 8`` slots."""
    tokens, d, k, held = 2 * 8192, 4096, 22, (0, 8)
    monkeypatch.setattr(kth, "serves", lambda *shape: True)
    layer = moe.MoEMlp(512, 2688, k, dtype=jnp.bfloat16, score="sigmoid",
                       route_scale=5.0, expert_act="relu2", latent=1024,
                       shared_ff=5376, held=held)
    x = jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16)
    variables, (x,) = _shapes_on(v5e_devices[0], layer.init, x)
    assert variables["params"]["up"].shape == (8, 1024, 2688)
    assert variables["buffers"]["choice_bias"].shape == (512,)

    def loss(params, x, buffers):
        out, aux = layer.apply({"params": params, "buffers": buffers}, x)
        assert aux == {}
        return jnp.mean(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables["params"], x, variables["buffers"]).compile().as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 11
    kth_calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line and "hvt_moe_kth" in line]
    assert len(kth_calls) == 1 and "/moe_route/" in kth_calls[0]
    sums = [line for line in text.splitlines()
            if "tpu_custom_call" in line and "hvt_moe_sum_by_token" in line]
    assert len(sums) == 2 and "/moe_combine/" in sums[0] and (
        "/moe_dispatch/" in sums[1]), sums
    assert "topk" not in text.lower() and f"[{tokens},{k},512]" not in text
    sorts = re.findall(r"= \(?\w+\[([\d,]*)\][^=]* sort\(", text)
    # (the others are over a round's T rows: the sums by token)
    assert [n for n in sorts if n != str(tokens)] == [str(tokens * 8)], sorts
    assert moe.held_rows(tokens, k, held, 512) == (8, tokens)
    assert f"bf16[{tokens},1024]" in text
    assert f"[{tokens * 8},1024]" not in text
    tiles = tokens // 512
    for line in text.splitlines():
        if " scatter(" in line:
            shape = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
            assert shape == f"{tokens},8" or (
                "," not in shape and int(shape) <= 2 * 8 + tiles), line


@pytest.mark.parametrize("shape", [
    pytest.param((2, 8192, 16, 32, 128, 128), id="gdn-qwen3next-s8192"),
    pytest.param((1, 1000, 4, 4, 128, 256), id="gdn-a-head-a-key-head-padded"),
])
def test_gated_delta_rule_kernels_compile_for_v5e(shape, compiled_kernel,
                                                  v5e_devices):
    """The three kernels of ``ops/gated_delta_rule.py`` at the benchmark
    cell qwen3next-s8192's own sizes (2 x 8192 positions, 32 value heads
    of 128 x 128 on 16 key heads, bf16, chunks of 128), forward and
    backward, and at a value head a key head with a wider value head and a
    length the chunk does not divide: both compile, in the default VMEM
    scope, and their temporaries are what the forward keeps for the
    backward (the states and the bf16 inverses, 403 MB at the cell's
    sizes) and the gradients on their way out, where the plain path keeps
    a dozen ``[c, c]`` float32 arrays of 268 MB."""
    b, s, h_k, h_v, d_k, d_v = shape
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    q, v = like(b, s, h_k, d_k), like(b, s, h_v, d_v)
    g = like(b, s, h_v, dtype=jnp.float32)

    def loss(*a):
        return jnp.mean(gdr.gated_delta_rule_kernels(*a, chunk=128).astype(
            jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, v, g, g).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert all(f"hvt_gdn_{kernel}" in text
               for kernel in ("inverse", "fwd", "bwd"))
    assert "vmem_limit_bytes" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_gdn_layers_share_one_lowered_kernel_under_their_scope(
        compiled_kernel, v5e_devices, monkeypatch):
    """Which program gets the kernels is decided from the backend, which
    is the CPU here, so the test steers it: a three-layer ``models.GPT``
    of Gated DeltaNet mixers at heads of 128 then holds four
    ``tpu_custom_call`` sites however many layers it has (the inverses',
    the forward's, the recomputed forward's and the backward's; the
    inverses are kept under ``remat`` and not made again), three kernels by
    name, and, compiled, each of the twelve calls' ``op_name`` has the scope
    ``gdn_rule`` in it (what ``chipbench/layer_metrics/gdn_rule_ms.py`` and
    ``gdn_ms.py`` match) and the pass it belongs to (what
    ``chipbench/regions.py`` splits the step by)."""
    from horovod_tpu.models import GPT, GPTConfig

    monkeypatch.setattr(gdr, "serves", lambda *shape: True)
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def step(pattern):
        model = GPT(GPTConfig(
            vocab_size=512, n_layers=len(pattern), layer_pattern=pattern,
            d_model=128, n_heads=2, d_ff=256, max_seq_len=256, remat=True,
            use_flash=False, gdn_key_heads=1, gdn_value_heads=2,
            gdn_key_dim=128, gdn_value_dim=128))
        tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32,
                                      sharding=one_chip)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.key(0), tokens))
        loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()
        return jax.jit(jax.grad(loss)).lower(params, tokens)

    one, three = step("G"), step("GGG")
    sites = lambda lowered: lowered.as_text().count(
        "stablehlo.custom_call @tpu_custom_call")
    assert sites(one) == sites(three) == 4, (sites(one), sites(three))
    assert set(re.findall(r"hvt_gdn_\w+", three.as_text())) == {
        "hvt_gdn_inverse", "hvt_gdn_fwd", "hvt_gdn_bwd"}
    # inlined, each call keeps its call site's whole op_name
    names = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"',
        three.compile().as_text())
    assert len(names) == 12 and all("/gdn_rule/" in n for n in names), names
    kinds = lambda kernel, inside: [
        n for n in names if f"/{kernel}/" in n and inside(n)]
    assert len(kinds("hvt_gdn_fwd", lambda n: "transpose(" not in n)) == 3
    assert len(kinds("hvt_gdn_inverse", lambda n: "transpose(" not in n)) == 3
    assert len(kinds("hvt_gdn_inverse", lambda n: True)) == 3
    assert len(kinds("hvt_gdn_fwd",
                     lambda n: "rematted_computation" in n)) == 3
    assert len(kinds("hvt_gdn_bwd", lambda n: "transpose(jvp(" in n
                     and "rematted_computation" not in n)) == 3


@pytest.mark.parametrize("shape", [
    pytest.param((2, 8192, 8192, False), id="conv-qwen3next-s8192"),
    pytest.param((2, 8192, 1280, True), id="conv-nemotron3s-s8192"),
    pytest.param((1, 2048, 1280, True), id="conv-nemotron3s-probe"),
    pytest.param((3, 384, 640, False), id="conv-a-block-of-384-by-640"),
])
def test_causal_conv_kernels_compile_for_v5e(shape, compiled_kernel,
                                             v5e_devices):
    """The two kernels of ``ops/causal_conv.py`` at the benchmark cells'
    own sizes (2 x 8192 positions of qwen3next-s8192's 8192 channels
    without a bias and of nemotron3s-s8192's 1280 with one, bf16, four
    taps), at the probes' 2048 positions and at a block that is no power
    of two, forward and backward: both compile with the blocks they derive,
    in the default VMEM scope, and the program's temporaries are smaller
    than one float32 array of the operand's size (the plain body pads one
    and keeps more)."""
    b, s, c, with_bias = shape
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    x, weight = like(b, s, c, dtype=jnp.bfloat16), like(4, c)
    bias = like(c) if with_bias else None

    def loss(*a):
        return jnp.mean(conv.causal_conv_kernels(*a).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2)[:2 + with_bias])
                       ).lower(x, weight, bias).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert all(f"hvt_causal_conv_{kernel}" in text
               for kernel in ("fwd", "bwd"))
    assert "vmem_limit_bytes" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * b * s * c


@pytest.mark.parametrize("pattern,scope,channels", [
    pytest.param("GGG", "gdn_conv", 512, id="gated-delta-net"),
    pytest.param("MMM", "ssm_conv", 256, id="mamba-2"),
])
def test_mixers_share_one_lowered_conv_kernel_under_their_scope(
        pattern, scope, channels, compiled_kernel, v5e_devices, monkeypatch):
    """Which program gets the convolution's kernels is decided from the
    backend, which is the CPU here, so the test steers it: a three-layer
    ``models.GPT`` of either mixer then holds three ``tpu_custom_call``
    sites however many layers it has (the forward's, the recomputed
    forward's and the backward's), two kernels by name, and, compiled, each
    of the nine calls' ``op_name`` has the mixer's convolution scope in it
    (``gdn_conv``: what ``chipbench/layer_metrics/gdn_ms.py`` matches;
    ``ssm_conv``: ``ssm_ms.py``) and the pass it belongs to (what
    ``chipbench/regions.py`` splits the step by)."""
    from horovod_tpu.models import GPT, GPTConfig

    monkeypatch.setattr(conv, "serves", lambda *shape: True)
    one_chip = SingleDeviceSharding(v5e_devices[0])

    def step(pattern):
        model = GPT(GPTConfig(
            vocab_size=512, n_layers=len(pattern), layer_pattern=pattern,
            d_model=128, n_heads=2, d_ff=256, max_seq_len=256, remat=True,
            use_flash=False, gdn_key_heads=1, gdn_value_heads=2,
            gdn_key_dim=128, gdn_value_dim=128, ssm_heads=2, ssm_head_dim=64,
            ssm_groups=1, ssm_state=64))
        tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32,
                                      sharding=one_chip)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.key(0), tokens))
        assert params["params"]["block_0"][scope[:3]]["conv_kernel"].shape \
            == (4, channels)
        loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()
        return jax.jit(jax.grad(loss)).lower(params, tokens)

    one, three = step(pattern[:1]), step(pattern)
    sites = lambda lowered: lowered.as_text().count(
        "stablehlo.custom_call @tpu_custom_call")
    assert sites(one) == sites(three) == 3, (sites(one), sites(three))
    assert set(re.findall(r"hvt_causal_conv_\w+", three.as_text())) == {
        "hvt_causal_conv_fwd", "hvt_causal_conv_bwd"}
    # inlined, each call keeps its call site's whole op_name
    names = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"',
        three.compile().as_text())
    assert len(names) == 9 and all(f"/{scope}/" in n for n in names), names
    kinds = lambda kernel, inside: [
        n for n in names if f"/{kernel}/" in n and inside(n)]
    assert len(kinds("hvt_causal_conv_fwd",
                     lambda n: "transpose(" not in n)) == 3
    assert len(kinds("hvt_causal_conv_fwd",
                     lambda n: "rematted_computation" in n)) == 3
    assert len(kinds("hvt_causal_conv_bwd", lambda n: "transpose(jvp(" in n
                     and "rematted_computation" not in n)) == 3


@pytest.mark.parametrize("shape", [
    pytest.param((2, 8192, 32, 128), id="norms-qwen3next-s8192-value-heads"),
    pytest.param((2, 8192, 16, 128), id="norms-qwen3next-s8192-key-heads"),
    pytest.param((1, 2048, 16, 128), id="norms-qwen3next-probe"),
    pytest.param((1, 1040, 3, 256), id="norms-a-ragged-last-block-at-256"),
    pytest.param((1, 2048, 2, 2048), id="norms-a-head-of-2048"),
])
def test_head_norm_kernels_compile_for_v5e(shape, compiled_kernel,
                                           v5e_devices):
    """The four kernels of ``ops/head_norm.py`` at the benchmark cell
    qwen3next-s8192's own sizes (2 x 8192 positions, 32 value heads and 16
    key heads of 128, bf16), at its probe's 2048 positions and at heads of
    256 over a sequence the block of 512 positions does not divide, and at
    a head wider than a block's 1024 lanes (fewer rows a block then),
    forward and backward: all compile with the blocks they derive, in the
    default VMEM scope, and the program's temporaries are no more than the
    two norms' bf16 results (nothing float32 of the operands' size)."""
    b, s, heads, dim = shape
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    o, w = like(b, s, heads * dim), like(dim, dtype=jnp.float32)

    def loss(o, z, w):
        y = head_norm.gated_norm_kernels(o, z, w, eps=1e-6)
        x = head_norm.l2_norm_kernels(o, dim, eps=1e-6, scale=dim ** -0.5)
        return jnp.mean(y.astype(jnp.float32) ** 2) + jnp.mean(
            x.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        o, o, w).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    assert all(f"hvt_{kernel}" in text for kernel in (
        "gated_norm_fwd", "gated_norm_bwd", "l2_norm_fwd", "l2_norm_bwd"))
    assert "vmem_limit_bytes" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        3 * 2 * b * s * heads * dim)


def test_gated_norm_with_a_sigmoid_gate_compiles_for_v5e(compiled_kernel,
                                                        v5e_devices):
    """The gated norm's two kernels with ``gate="sigmoid"`` at the cell
    kimilinear-s8192's own sizes (2 x 8192 positions, 32 heads of 128,
    bf16), forward and backward: the same two calls as ``silu``'s, in the
    default VMEM scope."""
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    o, w = like(2, 8192, 32 * 128), like(128, dtype=jnp.float32)

    def loss(o, z, w):
        return jnp.mean(head_norm.gated_norm_kernels(
            o, z, w, eps=1e-5, gate="sigmoid").astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        o, o, w).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "hvt_gated_norm_fwd" in text and "hvt_gated_norm_bwd" in text
    assert "vmem_limit_bytes" not in text


@pytest.mark.parametrize("body", ["kernels", "plain"])
def test_channel_delta_rule_compiles_for_v5e_within_its_passes(
        body, compiled_kernel, v5e_devices, monkeypatch):
    """The delta rule with a decay a channel at the cell kimilinear-s8192's
    heads (32 of 128 x 128, bf16, float32 decays) over one sequence of
    2,040 positions, which its chunks do not divide, forward and backward,
    through ``channel_delta_rule`` with ``serves`` steered (it is decided
    from the backend, which is the CPU here). The kernels: the three by
    name, one call each, in the default VMEM scope, and the temporaries
    are what the forward keeps for the backward (the states 34 MB, the
    bf16 inverses 17 MB) beside the padded operands and the gradients on
    their way out. The plain body, the path of every shape that does not
    serve (chunks of 32 here, what it was measured at): no kernel, and its
    temporaries are the operands, the gradients and one pass of 8 heads'
    chunks, 0.51 GiB, where all 32 heads at once are 1.2 GiB."""
    from horovod_tpu.ops import channel_delta_rule as rule

    kernels = body == "kernels"
    monkeypatch.setattr(rule, "serves", lambda *shape: kernels)
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    q = like(1, 2040, 32, 128)
    g = like(1, 2040, 32, 128, dtype=jnp.float32)
    beta = like(1, 2040, 32, dtype=jnp.float32)

    def loss(*a):
        return jnp.mean(rule.channel_delta_rule(
            *a, chunk=None if kernels else 32).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, q, g, beta).compile()
    text = compiled.as_text()
    if kernels:
        assert text.count("tpu_custom_call") == 3
        assert all(f"hvt_kda_{kernel}" in text
                   for kernel in ("inverse", "fwd", "bwd"))
        assert "vmem_limit_bytes" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * 2 ** 30
    else:
        assert "tpu_custom_call" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * 2 ** 30


def test_gdn_layers_hold_the_norm_kernels_and_stay_flat(
        compiled_kernel, v5e_devices, monkeypatch):
    """With the mixer's three choices steered to their kernels (they are
    decided from the backend, which is the CPU here), a three-layer
    ``models.GPT`` of Gated DeltaNet mixers at heads of 128 holds four
    more ``tpu_custom_call`` sites than the rule's and the convolution's,
    however many layers it has; compiled, the L2 norms' calls carry the
    scope ``gdn_rule`` and the gated norm's ``gdn_gate_norm`` in their
    ``op_name`` (what ``chipbench/layer_metrics/gdn_rule_ms.py`` and
    ``gdn_ms.py`` match) with the pass they belong to, and **nothing of an
    activation's size is reshaped or copied between the convolution's
    kernels and the out-projection**: the mixer's ``[b, s, H d]`` arrays go
    from custom call to custom call as they are."""
    from horovod_tpu.models import GPT, GPTConfig

    monkeypatch.setattr(gdr, "serves", lambda *shape: True)
    monkeypatch.setattr(conv, "serves", lambda *shape: True)
    monkeypatch.setattr(head_norm, "serves", lambda *shape: True)
    one_chip = SingleDeviceSharding(v5e_devices[0])
    batch, seq, heads = 2, 256, 4

    def step(pattern):
        model = GPT(GPTConfig(
            vocab_size=512, n_layers=len(pattern), layer_pattern=pattern,
            d_model=128, n_heads=2, d_ff=256, max_seq_len=seq, remat=True,
            use_flash=False, gdn_key_heads=2, gdn_value_heads=heads,
            gdn_key_dim=128, gdn_value_dim=128))
        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                      sharding=one_chip)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.key(0), tokens))
        loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()
        return jax.jit(jax.grad(loss)).lower(params, tokens)

    one, three = step("G"), step("GGG")
    sites = lambda lowered: lowered.as_text().count(
        "stablehlo.custom_call @tpu_custom_call")
    # the rule's 4 and the convolution's 3, then the L2 norm forward (at
    # q's scale and at k's), recomputed and backward, and the gated norm's
    # three
    assert sites(one) == sites(three) == 4 + 3 + 9, (sites(one), sites(three))
    text = three.compile().as_text()
    names = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    kinds = lambda kernel, inside: [
        n for n in names if f"/{kernel}/" in n and inside(n)]
    for kernel, scope, a_pass in (("hvt_l2_norm", "/gdn_rule/", 2),
                                  ("hvt_gated_norm", "/gdn_gate_norm/", 1)):
        calls = kinds(f"{kernel}_fwd", lambda n: True) + kinds(
            f"{kernel}_bwd", lambda n: True)
        assert len(calls) == 9 * a_pass and all(scope in n for n in calls)
        assert len(kinds(f"{kernel}_fwd", lambda n: "transpose(" not in n)
                   ) == 3 * a_pass
        assert len(kinds(f"{kernel}_fwd",
                         lambda n: "rematted_computation" in n)) == 3 * a_pass
        assert len(kinds(f"{kernel}_bwd", lambda n: "transpose(jvp(" in n
                         and "rematted_computation" not in n)) == 3 * a_pass
    # what is left to reshape or copy is of the model's width (128) or the
    # gates' [b, s, H_v]; q and k are twice that wide, v, z and o four times
    moved = re.findall(
        r"= \w+\[([\d,]+)\]\S* (?:reshape|copy|transpose)\(", text)
    size = lambda dims: int(np.prod([int(n) for n in dims.split(",")]))
    assert moved and max(map(size, moved)) <= batch * seq * 128, sorted(
        set(moved), key=size)[-3:]


# ---- attention over the keys an indexer chooses (keyevl2-s16384)

# (batch, seq, heads, kv_heads, d, d_v), the rotated pair's width, a choice,
# and how far under its estimate the backward alone still compiles: since
# PR 54 its loop carries nothing, and the compiler's least scope fell by
# 1.25 to 2.75 MiB (the carried dK, dV and dk_r were copies in VMEM beside
# their scratch). The parent's kernel needed 34.5, 38.0, 19.0 and 32.5 MiB
# at these four, this one 32.5, 35.25, 17.5 and 31.0 (compiler, PR 54)
@pytest.mark.parametrize("shape, e, choice, under", [
    pytest.param((2, 8192, 32, 32, 192, 128), 64, False, 1.5,
                 id="kanana2-s8192"),
    pytest.param((2, 8192, 16, 2, 256, 256), 0, False, 1.5,
                 id="qwen3next-s8192"),
    pytest.param((2, 4096, 20, 20, 64, 64), 0, False, 3.5,
                 id="gpt2l-s4096"),
    pytest.param((1, 16384, 32, 4, 128, 128), 0, True, 0.25,
                 id="keyevl2-s16384"),
])
def test_backward_alone_compiles_under_its_vmem_estimate(
        shape, e, choice, under, compiled_kernel, v5e_devices, monkeypatch):
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, h_kv, d, d_v = shape
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    q, do = like(b, h, s, d - e), like(b, h, s, d_v)
    k, v = like(b, h_kv, s, d - e), like(b, h_kv, s, d_v)
    lse = like(b, h, s, 1, dtype=jnp.float32)
    rotated = (like(b, h, s, e), like(b, s, e)) if e else None
    chosen = like(b, s, s, dtype=jnp.int8) if choice else None
    plan = fa._plan("bwd", q, d ** -0.5, True, None, None, d_v, e, choice)
    assert (plan.block_q, plan.block_k, plan.chains) == (1024, 512, 2)
    estimate = fa._vmem_bytes("bwd", 1024, 512, d, 2, plan.tile, s, d_v,
                              choice)
    limit = estimate - int(under * 2 ** 20)
    monkeypatch.setattr(fa, "_compiler_params", lambda *a: (
        pltpu.CompilerParams(vmem_limit_bytes=limit)))
    jax.clear_caches()
    try:
        text = fa._bwd_call.lower(q, k, v, do, lse, lse, rotated, chosen,
                                  plan=plan).compile().as_text()
    finally:
        jax.clear_caches()
    assert "hvt_flash_bwd" in text


DSA_SHAPE = dict(b=1, s=16384, h=32, h_kv=4, d=128, j=16, e=64, topk=2048)


def _dsa_like(device):
    sharding = SingleDeviceSharding(device)
    return lambda dtype, *dims: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=sharding)


def test_flash_kernels_with_a_choice_compile_for_v5e(compiled_kernel,
                                                     v5e_devices):
    """The cell's attention: 16,384 positions, 32 query heads on 4
    key-value heads of 128, a ``[1, 16384, 16384]`` int8 choice: forward
    and backward, one Pallas call each, the mask an operand of both."""
    like, z = _dsa_like(v5e_devices[0]), DSA_SHAPE
    q = like(jnp.bfloat16, z["b"], z["s"], z["h"], z["d"])
    kv = like(jnp.bfloat16, z["b"], z["s"], z["h_kv"], z["d"])
    choice = like(jnp.int8, z["b"], z["s"], z["s"])

    def loss(q, k, v, choice):
        return (fa.flash_attention(q, k, v, choice=choice).astype(
            jnp.float32) ** 2).mean()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, choice).compile().as_text()
    calls = re.findall(r" custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 2 and all("s8[" in call or "%" in call
                                   for call in calls)
    assert "hvt_flash_fwd" in text and "hvt_flash_bwd" in text


@pytest.mark.parametrize("window, tiles", [(2048, 3), (1024, 2)])
def test_flash_kernels_with_a_window_compile_for_v5e(window, tiles,
                                                     compiled_kernel,
                                                     v5e_devices):
    """``trinitymini-s16384``'s windowed calls and ``mellum2-s16384``'s:
    16,384 positions, 32 query heads on 4 key-value heads of 128, a window
    of 2,048 and of 1,024: forward and backward, one Pallas call each, and
    the schedule's second bound: both grids' last axis is three tiles of
    1,024 keys (rows), two at a window as wide as one tile (a query block
    of 512 sees 1,535 keys, which two tiles hold), where a full walk's is
    four of 4,096."""
    like, z = _dsa_like(v5e_devices[0]), DSA_SHAPE
    q = like(jnp.bfloat16, z["b"], z["s"], z["h"], z["d"])
    kv = like(jnp.bfloat16, z["b"], z["s"], z["h_kv"], z["d"])

    def step(window):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (fa.flash_attention(q, k, v, **window).astype(
                jnp.float32) ** 2).mean(), argnums=(0, 1, 2)))

    from test_flash_window import _grids

    walk = lambda window: _grids(step(window), q, kv, kv)
    assert walk({}) == [(1, 32, 32, 4), (1, 32, 32, 4)]
    assert walk({"window": window}) == [(1, 32, 32, tiles)] * 2
    text = step({"window": window}).lower(q, kv, kv).compile().as_text()
    calls = re.findall(r" custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 2
    assert "hvt_flash_fwd" in text and "hvt_flash_bwd" in text


@pytest.mark.parametrize("strict", [False, True],
                         ids=["clean-rows", "noised-rows-strict"])
def test_flash_kernels_with_blocks_compile_for_v5e(strict, compiled_kernel,
                                                   v5e_devices):
    """``sdar-s8192``'s two calls a layer: a copy's 8,192 positions, 32
    query heads on 4 key-value heads of 128, the causal limit by blocks of
    4, inclusive (the clean rows) and strict with the log-sum-exp and a
    float32 output (the noised rows on the clean keys): forward and
    backward, one Pallas call each, on the causal call's grids (two tiles
    of 4,096: the schedule is the causal one)."""
    like, z = _dsa_like(v5e_devices[0]), {**DSA_SHAPE, "s": 8192}
    q = like(jnp.bfloat16, z["b"], z["s"], z["h"], z["d"])
    kv = like(jnp.bfloat16, z["b"], z["s"], z["h_kv"], z["d"])

    def step(**mask):
        def loss(q, k, v):
            o, lse = fa.flash_attention_with_lse(
                q, k, v, **mask,
                **({"out_dtype": jnp.float32} if strict else {}))
            return ((o.astype(jnp.float32) ** 2).mean()
                    + jnp.where(jnp.isfinite(lse), lse, 0.0).mean() * strict)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    from test_flash_window import _grids

    assert _grids(step(), q, kv, kv) == [(1, 32, 16, 2), (1, 32, 16, 2)]
    assert _grids(step(blocks=4, strict=strict), q, kv, kv) == [
        (1, 32, 16, 2), (1, 32, 16, 2)]
    text = step(blocks=4, strict=strict).lower(q, kv, kv).compile().as_text()
    calls = re.findall(r" custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 2
    assert "hvt_flash_fwd" in text and "hvt_flash_bwd" in text


@pytest.mark.parametrize("rows", [512, 16384])
def test_choice_of_2048_keys_compiles_for_v5e(rows, compiled_kernel,
                                              v5e_devices):
    """The selection at ``[512, 16384]`` (a sequence's last rows, as one
    of several sequences' would be handed over) and at the cell's whole
    ``[16384, 16384]``: one Pallas call, no sort beside it."""
    from horovod_tpu.ops import dsa

    like, z = _dsa_like(v5e_devices[0]), DSA_SHAPE
    fn = lambda x: dsa._choice_call(x, topk=z["topk"], interpret=False)
    x = like(jnp.float32, rows, z["s"])
    text = jax.jit(fn).lower(x).compile().as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 1
    assert "hvt_dsa_choice" in text and " sort(" not in text


def test_index_scores_and_indexer_loss_compile_for_v5e(compiled_kernel,
                                                       v5e_devices):
    """16 index heads of 64 on one index key at 16,384 positions: the
    index scores' kernel, and the indexer's loss with its three gradients
    beside 32 heads on 4 of 128 (one Pallas call each: the value and the
    gradients leave together)."""
    from horovod_tpu.ops import dsa

    like, z = _dsa_like(v5e_devices[0]), DSA_SHAPE
    q_i = like(jnp.bfloat16, z["b"], z["s"], z["j"], z["e"])
    k_i = like(jnp.bfloat16, z["b"], z["s"], z["e"])
    w = like(jnp.float32, z["b"], z["s"], z["j"])
    text = jax.jit(dsa.index_scores).lower(q_i, k_i, w).compile().as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 1
    assert "hvt_dsa_index" in text

    def loss(q, k, lse, q_i, k_i, w, lse_i, choice):
        return dsa.index_loss(q, k, lse, q_i, k_i, w, lse_i, choice,
                              z["d"] ** -0.5)

    operands = (
        like(jnp.bfloat16, z["b"], z["s"], z["h"], z["d"]),
        like(jnp.bfloat16, z["b"], z["s"], z["h_kv"], z["d"]),
        like(jnp.float32, z["b"], z["s"], z["h"]), q_i, k_i, w,
        like(jnp.float32, z["b"], z["s"], 1),
        like(jnp.int8, z["b"], z["s"], z["s"]))
    compiled = jax.jit(loss).lower(*operands).compile()
    text = compiled.as_text()
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 1
    assert "hvt_dsa_loss" in text
    # nothing [s, s] wide but the operands: no float32 score matrix made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("shape", [
    pytest.param((8, 1024, 20, 20, 64, None), id="rotary-gpt2l-s1024"),
    pytest.param((2, 4096, 20, 20, 64, None), id="rotary-gpt2l-s4096"),
    pytest.param((1, 16384, 32, 4, 128, None), id="rotary-trinitymini-s16384"),
    pytest.param((2, 8192, 32, 0, 64, None), id="rotary-kanana2-s8192-q_r"),
    pytest.param((2, 8192, 16, 2, 256, 64), id="rotary-qwen3next-s8192"),
    pytest.param((1, 1040, 2, 1, 256, None),
                 id="rotary-five-blocks-of-208-the-partner-a-tile-away"),
])
def test_rotary_kernel_compiles_between_the_product_and_the_flash_kernels(
        shape, compiled_kernel, v5e_devices):
    """``ops/rotary.py``'s kernel at the benchmark cells' own sizes, bf16,
    between a projection's product and the flash kernels' transposition,
    forward and backward: one call a pass for ``q`` and ``k`` together
    (alone where there is no second array), in the default VMEM scope; the
    forward's operands are the products' own results, which XLA writes
    heads major, with no copy or transposition between, and the program's
    temporaries stay under two of ``q`` and ``k`` as a tile pads them (no
    float32 of that size, no second layout)."""
    b, s, h, h_k, dim, width = shape
    heads = tuple(n for n in (h, h_k) if n)
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    x, ws = like(b, s, 256), tuple(like(256, n, dim) for n in heads)
    gs = tuple(like(b, n, s, dim) for n in heads)

    def loss(ws, x, gs, positions):
        turned = rotary_op.rotary_kernels(
            tuple(jnp.einsum("bsd,dhk->bshk", x, w) for w in ws),
            positions, 1e4, width)
        # what flash_attention does with its operands
        return sum(jnp.sum((jnp.transpose(t, (0, 2, 1, 3)) * g).astype(
            jnp.float32) ** 2) for t, g in zip(turned, gs))

    compiled = jax.jit(jax.grad(loss)).lower(
        ws, x, gs, like(b, s, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    calls = {kernel: re.findall(
        rf" custom-call\(([^)]*)\).*tpu_custom_call.*hvt_rotary_{kernel}/",
        text) for kernel in ("fwd", "bwd")}
    assert len(calls["fwd"]) == len(calls["bwd"]) == 1
    assert text.count("tpu_custom_call") == 2
    operands = calls["fwd"][0].split(", ")[2:]      # after the two tables
    assert len(operands) == len(heads)
    for name in operands:       # each is made by the product itself
        made, = re.findall(rf"^ *{re.escape(name)} = .*$", text, re.M)
        assert "dot_general" in made and " copy(" not in made, made
    assert "vmem_limit_bytes" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.1 * (
        2 * b * s * sum(heads) * max(dim, 128))


def test_rotary_kernel_compiles_under_shard_map(compiled_kernel,
                                                v5e_devices):
    """gpt2l-dp4's rotary: four chips' batch under a ``shard_map`` that
    checks varying axes, the positions made inside it (every chip's the
    same: the tables vary over no mesh axis and the arrays over one): value
    and gradients compile with one forward and one
    backward call."""
    mesh = Mesh(np.array(v5e_devices), ("world",))
    rows = P("world")
    like = lambda heads: jax.ShapeDtypeStruct(
        (32, 1024, heads, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, rows))

    def per_chip(q, k):
        positions = jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2])
        return sum(jnp.mean(t.astype(jnp.float32) ** 2)
                   for t in rotary_op.rotary_kernels((q, k), positions, 1e4))

    step = jax.jit(jax.shard_map(
        lambda q, k: jax.tree.map(
            lambda x: jax.lax.pmean(x, "world"),
            jax.value_and_grad(per_chip, argnums=(0, 1))(q, k)),
        mesh=mesh, in_specs=(rows, rows), out_specs=P()))
    text = step.lower(like(20), like(20)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "hvt_rotary_fwd" in text and "hvt_rotary_bwd" in text


def test_sparse_attention_turns_q_and_k_by_the_kernel_in_whole_blocks(
        compiled_kernel, v5e_devices, monkeypatch):
    """``keyevl2-s16384``'s mixer at its published widths (32 heads on 4 of
    128, 16 index heads of 64, 16,384 positions) under ``remat``, as a TPU
    backend traces it: ``q`` and ``k`` go through ``hvt_rotary_fwd`` in one
    call (the first pass and the recomputed one) and their gradients
    through ``hvt_rotary_bwd``, beside the mixer's own kernels; the index
    queries (float32) and the index key (no head axis) stay plain; and
    **every block of every call divides the positions**. With blocks of
    112 the last step overhung the arrays by 80 positions, XLA had laid the
    ``sin`` table and the recomputed ``k`` at the last byte of VMEM
    (buffer assignment of the cell's step, PR 60), and the step never came
    back on the chip."""
    from horovod_tpu.models import GPT, GPTConfig

    z, one_chip = DSA_SHAPE, SingleDeviceSharding(v5e_devices[0])
    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    plans, real = [], rotary_op._turn
    monkeypatch.setattr(rotary_op, "_turn", lambda xs, cos, sin, plan: (
        plans.append((plan, [x.shape for x in xs])),
        real(xs, cos, sin, plan))[1])
    model = GPT(GPTConfig(
        vocab_size=512, n_layers=1, layer_pattern="S", d_model=2048,
        n_heads=z["h"], n_kv_heads=z["h_kv"], head_dim=z["d"], d_ff=256,
        max_seq_len=z["s"], remat=True, use_flash=True, rotary_base=1e7,
        dsa_index_heads=z["j"], dsa_index_dim=z["e"], dsa_topk=z["topk"]))
    tokens = jax.ShapeDtypeStruct((z["b"], z["s"]), jnp.int32,
                                  sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0), tokens))
    loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()
    text = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()
    assert plans and all(
        shapes == [(1, z["h"], z["s"], z["d"]), (1, z["h_kv"], z["s"], z["d"])]
        and z["s"] % plan.rows == 0 and plan.rows % plan.sub == 0
        for plan, shapes in plans), plans
    names = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    turns = [n for n in names if "/hvt_rotary_" in n]
    assert all("/dsa_proj/" in n for n in turns), turns
    assert sum("/hvt_rotary_fwd/" in n for n in turns) == 2
    assert sum("/hvt_rotary_bwd/" in n for n in turns) == 1
    for kernel in ("hvt_flash_fwd", "hvt_flash_bwd", "hvt_dsa_index",
                   "hvt_dsa_choice"):
        assert any(f"/{kernel}/" in n for n in names), kernel


@pytest.mark.parametrize("seq, dtype", [
    pytest.param(16384, jnp.bfloat16, id="scan-phi4flash-s16384"),
    pytest.param(16384, jnp.float32, id="scan-phi4flash-s16384-float32"),
    pytest.param(2048, jnp.bfloat16, id="scan-phi4flash-probe"),
    pytest.param(2048, jnp.float32, id="scan-phi4flash-probe-float32"),
])
def test_selective_scan_kernels_compile_for_v5e(seq, dtype, compiled_kernel,
                                                v5e_devices):
    """The two kernels of ``ops/selective_scan.py`` at ``phi4flash-s16384``'s
    own sizes (one sequence of 16,384 positions, 5,120 channels, a state of
    16) and at its probe's 2,048 positions, in bf16 and with float32
    operands (the probe runs both), forward and backward, with the blocks
    they derive: both compile inside the 24 MiB of VMEM they ask for
    (Mosaic refuses a kernel whose blocks and scratch do not fit its
    limit), and what the program holds beside its operands and results is
    the kept states and the sums, never ``[s, D, N]``."""
    from horovod_tpu.ops import selective_scan as scan_op

    channels, n = 5120, 16
    one = SingleDeviceSharding(v5e_devices[0])
    like = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one)
    operands = (like(1, seq, channels, dtype=dtype), like(1, seq, channels),
                like(channels, n), like(1, seq, n, dtype=dtype),
                like(1, seq, n, dtype=dtype))
    assert scan_op.VMEM_LIMIT <= 24 * 2 ** 20
    plan = scan_op._plan(channels, scan_op.CHUNK, None, None)
    assert (plan.tile, plan.fwd, plan.bwd) == (128, 512, 512)

    def loss(*a):
        return jnp.mean(scan_op.selective_scan_kernels(*a).astype(
            jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        *operands).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert all(f"hvt_mamba_scan_{kernel}" in text for kernel in ("fwd", "bwd"))
    # the kept states (s / 128 of [N, D] float32), this loss's own [s, D]
    # and little else: under an eighth of one [s, D, N] float32
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * seq * channels * n // 8


def test_mamba1_layer_lowers_with_the_scan_kernels_under_its_scope(
        compiled_kernel, v5e_devices, monkeypatch):
    """``phi4flash-s16384``'s ``Mamba1Mixer`` at its published widths
    (2,560 wide, 5,120 channels, a state of 16, bf16) at the probe's 2,048
    positions, as a TPU backend traces it (the backend is the CPU here, so
    the test steers the rule): the layer's gradient holds one
    ``hvt_mamba_scan_fwd`` and one ``hvt_mamba_scan_bwd``, both under
    ``mamba_scan`` (what ``chipbench/layer_metrics`` matches the scope
    by), beside the convolution's two under ``mamba_conv``; and the counter
    says ``body="kernels"``."""
    from horovod_tpu import metrics
    from horovod_tpu.models import mamba

    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    layer = mamba.Mamba1Mixer(expand=2, state=16, conv=4, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 2048, 2560), jnp.bfloat16)
    variables, (x,) = _shapes_on(v5e_devices[0], layer.init, x)

    def counted():
        m = metrics.registry().get("hvt_mamba_layers_traced_total")
        return m.labels(channels="5120", state="16", chunk="128",
                        body="kernels").value if m else 0.0

    def loss(params, x):
        out, memory = layer.apply({"params": params}, x)
        return jnp.mean(out.astype(jnp.float32) ** 2) + jnp.mean(
            memory.astype(jnp.float32))

    before = counted()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        variables["params"], x).compile().as_text()
    assert counted() == before + 1
    names = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    for kernel in ("hvt_mamba_scan_fwd", "hvt_mamba_scan_bwd"):
        mine = [n for n in names if f"/{kernel}/" in n]
        assert len(mine) == 1 and "/mamba_scan/" in mine[0], (kernel, names)
    assert sum("/mamba_conv/" in n for n in names) == 2, names


def test_selective_scan_kernels_bodies_stay_inside_the_starts_budget(
        compiled_kernel):
    """``_fwd_call`` and ``_bwd_call`` traced at ``phi4flash-s16384``'s own
    shape on the branch a TPU compiles (``interpret=False``: a CPU traces
    it, though it cannot compile it): the two together hold at most 1,000
    equations, loop and kernel bodies included
    (``benchmarks/selective_scan.py --count`` prints the same count). An
    equation of a kernel body costs the cell 0.9 ms of ``trace_s`` and 0.2
    ms of ``lower_s`` on every start, cached executable or not (ledger, PR
    67: bodies of 2,982 equations raised ``setup_s`` by 4.10 s of a bound
    of 3.16 and the PR was refused for it at +17% ``tok_s_chip``), so a
    later edit that unrolls a loop in Python fails here and not at the
    driver."""
    from benchmarks.selective_scan import equations
    from horovod_tpu.ops import selective_scan as scan_op

    seq, channels, n = 16384, 5120, 16
    like = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype)
    operands = (like(1, seq, channels, dtype=jnp.bfloat16),
                like(1, seq, channels), like(channels, n),
                like(1, seq, n, dtype=jnp.bfloat16),
                like(1, seq, n, dtype=jnp.bfloat16))
    plan = scan_op._plan(channels, scan_op.CHUNK, None, None)
    assert not plan.interpret
    fwd = jax.make_jaxpr(functools.partial(scan_op._fwd_call, plan=plan))(
        *operands)
    y, entered = fwd.out_avals
    bwd = jax.make_jaxpr(functools.partial(scan_op._bwd_call, plan=plan))(
        *operands, entered, y)
    counts = equations(fwd.jaxpr), equations(bwd.jaxpr)
    # a body is there to count: the recurrence's one exponential a position
    assert "exp2" in str(fwd) and "exp2" in str(bwd)
    assert sum(counts) <= 1000, counts
