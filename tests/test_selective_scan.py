"""The selective scan of ``ops/selective_scan.py`` and the Mamba-1 mixer
and gated memory unit of ``models/mamba.py``, at small sizes on the CPU:
the chunked body against the recurrence a position at a time (two chunk
lengths; a sequence the chunk does not divide is padded by rule), its
gradients against ``jax.grad`` of the plain recurrence, the float32 state
held by a case a bf16 one fails, nothing of ``[seq, D, N]`` in the traced
program forward or backward; the mixer forward and gradients against the
plain reference of ``chipbench/reference/phi4flash.py``; the leaves'
partition specs and the counter.

Suite clock (``PERF.md`` section 3's rule): 5 test-seconds, 9 CPU-seconds
(``os.times()`` around the file alone, PR 66)."""

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
from horovod_tpu.ops import selective_scan as scan_op

B, S, D, N = 2, 37, 24, 4


@functools.cache
def _operands(dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 5)
    u = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    # steps of 0.01 to 3 and decays of 1 to N: factors from 0.97 to e^-12
    delta = jnp.exp(jax.random.uniform(ks[1], (B, S, D), jnp.float32,
                                       math.log(1e-2), math.log(3.0)))
    a = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (D, N)) * jnp.exp(
        0.1 * jax.random.normal(ks[2], (D, N)))
    b = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    c = jax.random.normal(ks[4], (B, S, N), jnp.float32)
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


@pytest.mark.parametrize("chunk", [8, 16])
def test_gradients_are_those_of_the_plain_recurrence(chunk):
    """Every operand's gradient through the chunked body (a chunk's body
    recomputed from the state at its start) against ``jax.grad`` of the
    recurrence a position at a time; 1e-5: float32's own rounding over 37
    positions, a wrong carry or a wrong recomputation is of order 1."""
    weight = jax.random.normal(jax.random.key(9), (B, S, D))
    loss = lambda f: lambda *ops: jnp.sum(f(*ops) * weight)
    want = jax.grad(loss(scan_op.selective_scan_by_position),
                    argnums=range(5))(*_operands())
    got = jax.grad(loss(functools.partial(scan_op.selective_scan,
                                          chunk=chunk)),
                   argnums=range(5))(*_operands())
    for g, w in zip(got, want):
        assert _far(g, w) < 1e-5


def test_a_bf16_state_is_told_from_the_float32_one():
    """The state and the decays in bf16 (the case a wrong program fails)
    against float32 ones on the same float32 operands: the sound body is
    at float32's rounding of the recurrence, the wrong one a thousand
    times further. And bf16 operands, as the mixer hands them, come back
    as bf16."""
    want = scan_op.selective_scan_by_position(*_operands())
    sound = scan_op.selective_scan(*_operands(), chunk=8)
    wrong = scan_op.selective_scan(*_operands(), chunk=8,
                                   state_dtype=jnp.bfloat16)
    assert _far(sound, want) < 2e-6 < 2e-3 < _far(wrong, want)
    assert scan_op.selective_scan(*_operands(jnp.bfloat16),
                                  chunk=8).dtype == jnp.bfloat16


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


def test_nothing_of_seq_by_channels_by_state_is_held():
    """Forward and backward at 512 positions in chunks of 32: the largest
    array is a chunk's ``[32, D, N]`` or an operand, never ``[512, D, N]``;
    and the backward pass keeps ``512 / 32`` states."""
    s = 512
    like = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    shapes = (like(1, s, D), like(1, s, D), like(D, N), like(1, s, N),
              like(1, s, N))
    f = functools.partial(scan_op.selective_scan, chunk=32)
    whole = s * D * N
    fwd = jax.make_jaxpr(f)(*shapes)
    bwd = jax.make_jaxpr(jax.grad(lambda *o: f(*o).sum(),
                                  argnums=range(5)))(*shapes)
    assert _largest(fwd.jaxpr) <= s * D < whole
    assert _largest(bwd.jaxpr) <= s * D
    assert f"f32[{s // 32},1,{N},{D}]" in str(bwd)      # the kept states


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


@pytest.mark.parametrize("chunk", [8, 16])
def test_mixer_is_the_reference_forward_and_backward(chunk):
    layer, params, x = _mixer(chunk)
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

    def counted():
        m = metrics.registry().get("hvt_mamba_layers_traced_total")
        return m.labels(channels=str(inner), state=str(N), chunk=str(S),
                        body="plain").value if m else 0.0

    before = counted()
    jax.eval_shape(lambda p: layer.apply({"params": p}, x), params)
    assert counted() == before + 1


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
