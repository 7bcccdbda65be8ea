"""The selective scan of ``ops/selective_scan.py`` and the Mamba-1 mixer
and gated memory unit of ``models/mamba.py``, at small sizes on the CPU,
**both bodies**, the plain one and the Pallas kernels interpreted: the
chunked body against the recurrence a position at a time (a sequence the
chunk does not divide is padded by rule; bf16 and float32 operands), its
gradients against ``jax.grad`` of the plain recurrence (``db`` and ``dc``,
the kernels' sums over every channel, among them), the float32 state held
by a case a bf16 one fails, nothing of ``[seq, D, N]`` in the traced
program forward or backward; ``serves``'s rule shape by shape; the mixer
forward and gradients against the plain reference of
``chipbench/reference/phi4flash.py`` by either body; the leaves' partition
specs and the counter.

Suite clock (``PERF.md`` section 3's rule): 5 test-seconds, 9 CPU-seconds
(``os.times()`` around the file alone, PR 66); with the kernels' cases,
interpreted, 50 test-seconds and 125 CPU-seconds, no test over 6 (PR 68)."""

import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import phi4flash as reference
from horovod_tpu import metrics
from small_models import random_tree
from horovod_tpu.models import mamba
from horovod_tpu.ops import _pallas
from horovod_tpu.ops import selective_scan as scan_op

B, S, D, N = 2, 37, 24, 4
# what the kernels' cases run at: whole registers (a state of 16, channels
# in tiles of 128 lanes), a sequence no chunk here divides
KERNELS = (2, 200, 256, 16)
BODIES = {"plain": scan_op.selective_scan_plain,
          "kernels": scan_op.selective_scan_kernels}


@functools.cache
def _operands(dtype=jnp.float32, shape=(B, S, D, N)):
    batch, seq, channels, n = shape
    ks = jax.random.split(jax.random.key(0), 5)
    u = jax.random.normal(ks[0], (batch, seq, channels), jnp.float32)
    # steps of 0.01 to 3 and decays of 1 to N: factors from 0.97 to e^-48
    delta = jnp.exp(jax.random.uniform(
        ks[1], (batch, seq, channels), jnp.float32, math.log(1e-2),
        math.log(3.0)))
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (channels, n)) * jnp.exp(
        0.1 * jax.random.normal(ks[2], (channels, n)))
    b = jax.random.normal(ks[3], (batch, seq, n), jnp.float32)
    c = jax.random.normal(ks[4], (batch, seq, n), jnp.float32)
    return u.astype(dtype), delta, a, b.astype(dtype), c.astype(dtype)


def _far(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


# chunks that divide nothing here: 37 positions are padded to 40 and 48, a
# chunk of 12 ends in a pass of 4, and a chunk longer than the sequence is
# the sequence
@pytest.mark.parametrize("chunk", [8, 12, 16, 64])
def test_chunked_scan_is_the_recurrence(chunk):
    want = scan_op.selective_scan_by_position(*_operands())
    got = scan_op.selective_scan(*_operands(), chunk=chunk)
    assert got.shape == want.shape and _far(got, want) < 2e-6


# the kernels' tile is the chunk: 200 positions are padded to 256 either
# way, four tiles of 64 (a carry from tile to tile, forward and back) or
# two of 128; bf16 operands come back rounded to bf16 once
@pytest.mark.parametrize("chunk, dtype, near", [
    (64, jnp.float32, 2e-6), (64, jnp.bfloat16, 4e-3),
    (128, jnp.float32, 2e-6)])
def test_kernels_are_the_recurrence(chunk, dtype, near):
    operands = _operands(dtype, KERNELS)
    want = scan_op.selective_scan_by_position(*operands)
    got = scan_op.selective_scan_kernels(*operands, chunk=chunk)
    assert got.shape == want.shape and got.dtype == dtype
    assert _far(got, want) < near


def test_kernels_take_the_blocks_a_caller_names():
    """Blocks of 128 channels, going forward and back: two blocks a tile,
    so the tile's columns made at the first serve the second and ``db``,
    ``dc`` add up over both."""
    operands = _operands(jnp.float32, KERNELS)
    weight = jax.random.normal(jax.random.key(9), operands[0].shape)
    loss = lambda f: lambda *ops: jnp.sum(f(*ops) * weight)
    named = functools.partial(scan_op.selective_scan_kernels, chunk=64,
                              fwd=128, bwd=128)
    want = jax.value_and_grad(loss(scan_op.selective_scan_by_position),
                              argnums=range(5))(*operands)
    got = jax.value_and_grad(loss(named), argnums=range(5))(*operands)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _far(g, w) < 1e-5


@pytest.mark.parametrize("body, chunk, shape", [
    ("plain", 8, (B, S, D, N)), ("plain", 16, (B, S, D, N)),
    ("kernels", 64, KERNELS), ("kernels", 128, KERNELS)])
def test_gradients_are_those_of_the_plain_recurrence(body, chunk, shape):
    """Every operand's gradient through the chunked body (a chunk's body
    recomputed from the state at its start) against ``jax.grad`` of the
    recurrence a position at a time; 1e-5: float32's own rounding over the
    positions, a wrong carry or a wrong recomputation is of order 1. The
    kernels' ``db`` and ``dc`` are sums over every channel made lane by
    lane and folded; their ``da`` stays in place over a sequence's tiles."""
    operands = _operands(jnp.float32, shape)
    weight = jax.random.normal(jax.random.key(9), operands[0].shape)
    loss = lambda f: lambda *ops: jnp.sum(f(*ops) * weight)
    want = jax.grad(loss(scan_op.selective_scan_by_position),
                    argnums=range(5))(*operands)
    got = jax.grad(loss(functools.partial(BODIES[body], chunk=chunk)),
                   argnums=range(5))(*operands)
    for g, w in zip(got, want):
        assert g.shape == w.shape and _far(g, w) < 1e-5


def test_kernels_gradients_in_bf16_are_the_plain_bodys():
    """bf16 operands, as the mixer hands them: both bodies round ``y`` and
    the output's gradient to bf16 and nothing else, so the kernels'
    gradients stand from the plain body's at bf16's rounding of ``du``,
    ``db``, ``dc`` and at float32's for ``ddelta`` and ``da``."""
    operands = _operands(jnp.bfloat16, KERNELS)
    weight = jax.random.normal(jax.random.key(9), operands[0].shape)
    loss = lambda f: lambda *ops: jnp.sum(
        f(*ops, chunk=64).astype(jnp.float32) * weight)
    want = jax.grad(loss(BODIES["plain"]), argnums=range(5))(*operands)
    got = jax.grad(loss(BODIES["kernels"]), argnums=range(5))(*operands)
    for g, w, near in zip(got, want, (4e-3, 1e-5, 1e-5, 4e-3, 4e-3)):
        assert g.dtype == w.dtype
        assert _far(g, w.astype(jnp.float32)) < near


@pytest.mark.parametrize("body, chunk, shape", [
    ("plain", 8, (B, S, D, N)), ("kernels", 64, KERNELS)])
def test_a_bf16_state_is_told_from_the_float32_one(body, chunk, shape):
    """The state and the decays in bf16 (the case a wrong program fails)
    against float32 ones on the same float32 operands: the sound body is
    at float32's rounding of the recurrence, the wrong one a thousand
    times further (the kernels have no bf16 state: asked for one,
    ``selective_scan`` takes the plain body). And bf16 operands, as the
    mixer hands them, come back as bf16."""
    operands = _operands(jnp.float32, shape)
    want = scan_op.selective_scan_by_position(*operands)
    sound = BODIES[body](*operands, chunk=chunk)
    wrong = scan_op.selective_scan(*operands, chunk=chunk,
                                   state_dtype=jnp.bfloat16)
    assert _far(sound, want) < 2e-6 < 2e-3 < _far(wrong, want)
    assert BODIES[body](*_operands(jnp.bfloat16, shape),
                        chunk=chunk).dtype == jnp.bfloat16


def test_serves_by_shape_dtype_and_backend(monkeypatch):
    """The rule: a TPU, the kernels' chunk and a sequence it divides,
    channels in whole groups, a state of whole registers, bf16 or float32
    operands, a float32 state; everything else, and everything on the CPU,
    is the plain body's."""
    cell = dict(seq_len=16384, channels=5120, state=16, chunk=128,
                dtype=jnp.bfloat16)
    assert not scan_op.serves(**cell)                    # the CPU
    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    assert scan_op.serves(**cell)
    assert scan_op.serves(**{**cell, "seq_len": 2048, "dtype": jnp.float32})
    for other in (dict(seq_len=16384 + 64), dict(chunk=64), dict(chunk=256),
                  dict(channels=5120 + 128), dict(state=8), dict(state=24),
                  dict(dtype=jnp.float16),
                  dict(state_dtype=jnp.bfloat16)):
        assert not scan_op.serves(**{**cell, **other}), other
    # and the choice: a sequence the chunk does not divide, or a bf16
    # state, goes to the plain body without a kernel traced
    operands = _operands(jnp.float32, (1, 200, 512, 16))
    kernels = lambda **kw: "hvt_mamba_scan_fwd" in str(jax.make_jaxpr(
        functools.partial(scan_op.selective_scan, **kw))(*operands))
    assert not kernels() and not kernels(chunk=128)
    whole = tuple(t[:, :128] if t.ndim == 3 else t for t in operands)
    traced = lambda **kw: "hvt_mamba_scan_fwd" in str(jax.make_jaxpr(
        functools.partial(scan_op.selective_scan, **kw))(*whole))
    assert traced() and traced(chunk=128)
    assert not traced(chunk=64) and not traced(state_dtype=jnp.bfloat16)


def _largest(jaxpr) -> int:
    """Elements of the largest array any equation of ``jaxpr`` makes, loop
    bodies included."""
    from chipbench.flops import _sub_jaxprs

    most = 0
    for eqn in jaxpr.eqns:
        most = max([most] + [math.prod(v.aval.shape) for v in eqn.outvars
                             if hasattr(v.aval, "shape")]
                   + [_largest(inner) for inner in _sub_jaxprs(eqn)])
    return most


@pytest.mark.parametrize("body, d, n, kept", [
    ("plain", D, N, "f32[{chunks},1,{n},{d}]"),
    ("kernels", 256, 16, "f32[1,{chunks},2,{n},128]")])
def test_nothing_of_seq_by_channels_by_state_is_held(body, d, n, kept):
    """Forward and backward at 512 positions in chunks of 32: the largest
    array is a chunk's ``[32, D, N]`` or an operand, never ``[512, D, N]``
    (the kernels' bodies included: a tile's states are scratch of ``[33,
    block / 128, N, 128]``); and the backward pass keeps ``512 / 32``
    states."""
    s = 512
    like = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    shapes = (like(1, s, d), like(1, s, d), like(d, n), like(1, s, n),
              like(1, s, n))
    f = functools.partial(BODIES[body], chunk=32)
    whole = s * d * n
    fwd = jax.make_jaxpr(f)(*shapes)
    bwd = jax.make_jaxpr(jax.grad(lambda *o: f(*o).sum(),
                                  argnums=range(5)))(*shapes)
    assert _largest(fwd.jaxpr) <= s * d < whole
    assert _largest(bwd.jaxpr) <= s * d
    assert kept.format(chunks=s // 32, n=n, d=d) in str(bwd)
    if body == "kernels":
        assert f"f32[33,2,{n},128]" in str(bwd)          # a tile's states


# ---- the mixer and the unit against the plain reference

D_MODEL = 16


@functools.cache
def _mixer(chunk=None):
    layer = mamba.Mamba1Mixer(expand=2, state=N, conv=4, rank=3, chunk=chunk,
                              dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(3), (2, S, D_MODEL))
    # seeded random leaves by the tree's shapes: the biases away from 0,
    # the skip term from 1, any A_log a decay
    params = random_tree(jax.eval_shape(layer.init, jax.random.key(4),
                                        x)["params"], 5, 0.2)
    return layer, params, x


def _mixer_is_the_reference(layer, params, x):
    weight = jax.random.normal(jax.random.key(6), x.shape)

    def mine(p, x):
        out, memory = layer.apply({"params": p}, x)
        return jnp.sum(out * weight) + jnp.sum(memory), (out, memory)

    def plain(p, x):
        out, memory = jax.vmap(lambda one: reference.mamba(one, p))(x)
        return jnp.sum(out * weight) + jnp.sum(memory), (out, memory)

    with jax.default_matmul_precision("highest"):
        (_, got), g = jax.jit(jax.value_and_grad(
            mine, (0, 1), has_aux=True))(params, x)
        (_, want), w = jax.jit(jax.value_and_grad(
            plain, (0, 1), has_aux=True))(params, x)
    # float32's own rounding; a wrong tap, gate or skip term is of order 1
    assert _far(got[0], want[0]) < 1e-5 and _far(got[1], want[1]) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(w)):
        assert _far(a, b) < 1e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("chunk", [8, 16])
def test_mixer_is_the_reference_forward_and_backward(chunk):
    _mixer_is_the_reference(*_mixer(chunk))


def _counted(channels, state, chunk, body):
    m = metrics.registry().get("hvt_mamba_layers_traced_total")
    return m.labels(channels=str(channels), state=str(state),
                    chunk=str(chunk), body=body).value if m else 0.0


def test_mixer_takes_the_kernels_where_the_rule_says_so(monkeypatch):
    """A mixer of 512 channels and a state of 16 over 128 positions, as a
    TPU backend traces it (the CPU here, so the test steers the rule; the
    kernels are interpreted): forward and gradients are the reference's,
    the skip term, the gate and the rounding of ``m`` where they were, and
    the counter says ``body="kernels"``."""
    monkeypatch.setattr(_pallas, "on_tpu", lambda: True)
    layer = mamba.Mamba1Mixer(expand=2, state=16, conv=4, rank=3,
                              dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(3), (1, 128, 256))
    params = random_tree(jax.eval_shape(layer.init, jax.random.key(4),
                                        x)["params"], 5, 0.2)
    before = _counted(512, 16, 128, "kernels")
    _mixer_is_the_reference(layer, params, x)
    assert _counted(512, 16, 128, "kernels") == before + 1


def test_mixer_has_its_leaves_their_specs_and_its_count():
    layer, params, x = _mixer()
    inner, rank = 2 * D_MODEL, 3
    assert jax.tree.map(jnp.shape, params) == {
        "in_proj": (D_MODEL, 2 * inner), "conv_kernel": (4, inner),
        "conv_bias": (inner,), "x_proj": (inner, rank + 2 * N),
        "dt_proj": (rank, inner), "dt_bias": (inner,), "A_log": (inner, N),
        "D_skip": (inner,), "out_proj": (inner, D_MODEL)}
    assert mamba.step_rank(2560) == 160 and mamba.step_rank(2560, 7) == 7
    # Mamba-1's own initialisation: A[c, n] = n + 1, the skip term 1
    fresh = jax.jit(layer.init)(jax.random.key(4), x)["params"]
    assert jnp.allclose(jnp.exp(fresh["A_log"]), jnp.arange(1.0, N + 1))
    assert jnp.all(fresh["D_skip"] == 1) and jnp.all(fresh["dt_bias"] < 0)
    specs = {name: mamba.mamba_leaf_spec(name, "tp") for name in params}
    assert specs == {
        "in_proj": P(), "conv_kernel": P(None, "tp"), "conv_bias": P("tp"),
        "x_proj": P("tp", None), "dt_proj": P(None, "tp"),
        "dt_bias": P("tp"), "A_log": P("tp", None), "D_skip": P("tp"),
        "out_proj": P("tp", None)}
    assert {n: mamba.gmu_leaf_spec(n, "tp") for n in ("in_proj", "out_proj")
            } == {"in_proj": P(None, "tp"), "out_proj": P("tp", None)}

    before = _counted(inner, N, S, "plain")
    jax.eval_shape(lambda p: layer.apply({"params": p}, x), params)
    assert _counted(inner, N, S, "plain") == before + 1


def test_unit_is_the_reference():
    unit = mamba.GatedMemoryUnit(dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(7), (2, S, D_MODEL))
    memory = jax.random.normal(jax.random.key(8), (2, S, 2 * D_MODEL))
    params = random_tree(jax.eval_shape(
        unit.init, jax.random.key(9), x, memory)["params"],
        9, 0.2)
    assert jax.tree.map(jnp.shape, params) == {
        "in_proj": (D_MODEL, 2 * D_MODEL), "out_proj": (2 * D_MODEL, D_MODEL)}
    with jax.default_matmul_precision("highest"):
        got = unit.apply({"params": params}, x, memory)
        want = reference.unit(x, memory, params)
    assert _far(got, want) < 1e-6
