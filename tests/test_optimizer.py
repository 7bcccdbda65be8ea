"""DistributedOptimizer tests — gradient reduction semantics
(reference ``test/parallel/test_torch.py`` optimizer tests +
``tensorflow/gradient_aggregation.py`` behavior)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu.ops.compression import Compression
from horovod_tpu.parallel.mesh import WORLD_AXIS

N = 8


def _sgd_step_fn(tx, mesh, params_shape=(3,)):
    # check_vma=False: the aggregation cond mixes varying/invariant values
    # (see DistributedGradientTransformation docstring).
    def step(params, opt_state, grads_per_rank):
        def inner(p, s, g):
            updates, new_s = tx.update(g[0], s, p)
            new_p = optax.apply_updates(p, updates)
            return new_p, new_s

        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), P(WORLD_AXIS)),
            out_specs=(P(), P()), check_vma=False)(
                params, opt_state, grads_per_rank)

    return jax.jit(step)


def test_distributed_sgd_averages_gradients(world_mesh):
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS)
    params = jnp.zeros((3,))
    opt_state = tx.init(params)
    rng = np.random.RandomState(0)
    grads = rng.randn(N, 3).astype(np.float32)
    step = _sgd_step_fn(tx, world_mesh)
    new_params, _ = step(params, opt_state, grads)
    np.testing.assert_allclose(np.asarray(new_params),
                               -grads.mean(axis=0), rtol=1e-5)


def test_distributed_sgd_sum_op(world_mesh):
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS,
                                  op=hvt.Sum)
    params = jnp.zeros((3,))
    opt_state = tx.init(params)
    grads = np.ones((N, 3), np.float32)
    step = _sgd_step_fn(tx, world_mesh)
    new_params, _ = step(params, opt_state, grads)
    np.testing.assert_allclose(np.asarray(new_params), -N * np.ones(3),
                               rtol=1e-5)


def test_gradient_predivide_factor(world_mesh):
    # predivide splits the averaging between pre and post scaling
    # (reference tensorflow/__init__.py:578-590); result == plain average
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS,
                                  gradient_predivide_factor=2.0)
    params = jnp.zeros((3,))
    opt_state = tx.init(params)
    rng = np.random.RandomState(1)
    grads = rng.randn(N, 3).astype(np.float32)
    step = _sgd_step_fn(tx, world_mesh)
    new_params, _ = step(params, opt_state, grads)
    np.testing.assert_allclose(np.asarray(new_params),
                               -grads.mean(axis=0), rtol=1e-5)


def test_compression_roundtrip(world_mesh):
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS,
                                  compression=Compression.fp16)
    params = jnp.zeros((3,))
    opt_state = tx.init(params)
    grads = np.full((N, 3), 0.5, np.float32)
    step = _sgd_step_fn(tx, world_mesh)
    new_params, _ = step(params, opt_state, grads)
    assert new_params.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(new_params), -0.5 * np.ones(3),
                               rtol=1e-3)


def test_backward_passes_per_step(world_mesh):
    # accumulate 2 steps locally, apply on the 2nd
    # (reference gradient_aggregation.py:16)
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS,
                                  backward_passes_per_step=2)
    params = jnp.zeros((3,))
    opt_state = tx.init(params)
    grads = np.ones((N, 3), np.float32)
    step = _sgd_step_fn(tx, world_mesh)
    p1, s1 = step(params, opt_state, grads)
    # first call: held — no update applied
    np.testing.assert_allclose(np.asarray(p1), 0.0)
    p2, s2 = step(p1, s1, grads)
    # second call: sum of 2 accumulated unit grads, averaged over ranks = 2
    np.testing.assert_allclose(np.asarray(p2), -2.0 * np.ones(3), rtol=1e-5)
    # counter keeps cycling
    p3, s3 = step(p2, s2, grads)
    np.testing.assert_allclose(np.asarray(p3), np.asarray(p2))


def test_backward_passes_average_aggregated(world_mesh):
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS,
                                  backward_passes_per_step=2,
                                  average_aggregated_gradients=True)
    params = jnp.zeros((3,))
    opt_state = tx.init(params)
    grads = np.ones((N, 3), np.float32)
    step = _sgd_step_fn(tx, world_mesh)
    p1, s1 = step(params, opt_state, grads)
    p2, _ = step(p1, s1, grads)
    np.testing.assert_allclose(np.asarray(p2), -1.0 * np.ones(3), rtol=1e-5)


def test_adam_state_held_between_aggregation_steps(world_mesh):
    # the inner optimizer state must NOT advance on held steps
    tx = hvt.DistributedOptimizer(optax.adam(0.1), axis_name=WORLD_AXIS,
                                  backward_passes_per_step=3)
    params = jnp.zeros((2,))
    opt_state = tx.init(params)
    grads = np.ones((N, 2), np.float32)
    step = _sgd_step_fn(tx, world_mesh, params_shape=(2,))
    p, s = step(params, opt_state, grads)
    inner_count_after_1 = int(np.asarray(
        jax.tree.leaves(s.inner_state)[0]))
    p, s = step(p, s, grads)
    p, s = step(p, s, grads)
    # after 3 calls exactly one inner update happened
    counts = [x for x in jax.tree.leaves(s.inner_state)
              if np.asarray(x).ndim == 0]
    assert inner_count_after_1 == 0
    assert int(np.asarray(counts[0])) == 1


def test_partial_distributed_optimizer(world_mesh):
    tx = hvt.PartialDistributedGradientTransformation(
        optax.sgd(1.0), local_layers=("local",), axis_name=WORLD_AXIS)
    params = {"shared": jnp.zeros((2,)), "local": jnp.zeros((2,))}
    opt_state = tx.init(params)

    def step(params, opt_state, grads_per_rank):
        def inner(p, s, g):
            updates, new_s = tx.update(
                jax.tree.map(lambda x: x[0], g), s, p)
            new_p = optax.apply_updates(p, updates)
            # local params legitimately differ per shard → per-shard output
            return new_p["shared"], new_p["local"][None]

        return jax.shard_map(
            inner, mesh=world_mesh,
            in_specs=(P(), P(), {"shared": P(WORLD_AXIS),
                                 "local": P(WORLD_AXIS)}),
            out_specs=(P(), P(WORLD_AXIS)),
            check_vma=False)(params, opt_state, grads_per_rank)

    grads = {"shared": np.ones((N, 2), np.float32),
             "local": np.arange(2 * N, dtype=np.float32).reshape(N, 2)}
    shared_p, local_p = jax.jit(step)(params, opt_state, grads)
    # shared: averaged (= 1); local: each shard applied its own grad
    np.testing.assert_allclose(np.asarray(shared_p), -1.0)
    np.testing.assert_allclose(np.asarray(local_p), -grads["local"],
                               rtol=1e-6)


def test_grad_of_replicated_params_not_double_counted(world_mesh):
    # Under default shard_map (check_vma=True) AD already psums grads of
    # replicated params; the optimizer must divide, not re-reduce.
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS)
    rng = np.random.RandomState(5)
    X = rng.randn(N, 4).astype(np.float32)

    def per_shard(p, s, x):
        loss_fn = lambda p: jnp.mean((p * x[0]) ** 2)
        g = jax.grad(loss_fn)(p)
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    params = jnp.asarray(2.0)
    opt_state = tx.init(params)
    f = jax.jit(jax.shard_map(per_shard, mesh=world_mesh,
                              in_specs=(P(), P(), P(WORLD_AXIS)),
                              out_specs=(P(), P())))
    new_p, _ = f(params, opt_state, X)
    per_shard_grads = np.array([np.mean(2 * 2.0 * x * x) for x in X])
    np.testing.assert_allclose(float(new_p),
                               2.0 - per_shard_grads.mean(), rtol=1e-5)


def test_grad_predivide_with_vma_reduced_grads(world_mesh):
    tx = hvt.DistributedOptimizer(optax.sgd(1.0), axis_name=WORLD_AXIS,
                                  gradient_predivide_factor=4.0)
    rng = np.random.RandomState(6)
    X = rng.randn(N, 4).astype(np.float32)

    def per_shard(p, s, x):
        g = jax.grad(lambda p: jnp.mean((p * x[0]) ** 2))(p)
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s

    params = jnp.asarray(1.0)
    opt_state = tx.init(params)
    f = jax.jit(jax.shard_map(per_shard, mesh=world_mesh,
                              in_specs=(P(), P(), P(WORLD_AXIS)),
                              out_specs=(P(), P())))
    new_p, _ = f(params, opt_state, X)
    per_shard_grads = np.array([np.mean(2 * 1.0 * x * x) for x in X])
    np.testing.assert_allclose(float(new_p),
                               1.0 - per_shard_grads.mean(), rtol=1e-5)


def test_allreduce_gradients_no_axis_is_local():
    from horovod_tpu.jax import allreduce_gradients

    g = {"w": jnp.ones((2, 2))}
    out = jax.jit(lambda g: allreduce_gradients(g, axis_name=None))(g)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)


def test_allreduce_gradients_raises_when_vma_probe_cannot_answer(
        world_mesh, monkeypatch):
    # ROADMAP D7: if a JAX release moves the varying-axes typing, the probe
    # must fail the step. Answering "not reduced" instead would psum every
    # replicated parameter's gradient a second time, silently.
    from horovod_tpu.jax import allreduce_gradients

    monkeypatch.setattr(jax, "typeof", lambda x: object())

    def per_shard(p, x):
        g = jax.grad(lambda p: jnp.mean((p * x[0]) ** 2))(p)
        return allreduce_gradients(g, axis_name=WORLD_AXIS)

    f = jax.jit(jax.shard_map(per_shard, mesh=world_mesh,
                              in_specs=(P(), P(WORLD_AXIS)),
                              out_specs=P()))
    with pytest.raises(AttributeError, match="vma"):
        f(jnp.asarray(2.0), jnp.ones((N, 4), jnp.float32))


def _op_names(lowered):
    import re

    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


@pytest.mark.parametrize("passes", [1, 2])
def test_compiled_step_names_the_reduction_and_the_update(
        world_mesh, passes, monkeypatch):
    # The two scopes are the package's contract with a trace reader
    # (chipbench/regions.py): every operation of the reduction and of the
    # wrapped optimizer carries its scope in the compiled step, also on
    # the accumulating path's emit branch.
    import contextlib

    from horovod_tpu.jax import REDUCE_SCOPE, UPDATE_SCOPE

    def lowered():
        tx = hvt.DistributedOptimizer(
            optax.adamw(1e-3), axis_name=WORLD_AXIS,
            backward_passes_per_step=passes)

        def per_shard(p, s, x):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((x @ p["w"]) ** 2))(p)
            updates, s = tx.update(g, s, p)
            return (optax.apply_updates(p, updates), s,
                    jax.lax.pmean(loss, WORLD_AXIS))

        params = {"w": jnp.ones((4, 3))}
        return jax.jit(jax.shard_map(
            per_shard, mesh=world_mesh, in_specs=(P(), P(), P(WORLD_AXIS)),
            out_specs=P(), check_vma=False)).lower(
                params, tx.init(params), jnp.ones((N, 4), jnp.float32))

    step = lowered()
    names = _op_names(step)
    reduce = [n for n in names if REDUCE_SCOPE in n]
    update = [n for n in names if UPDATE_SCOPE in n]
    assert reduce and update, sorted(names)
    # neither scope is inside the other, nor inside autodiff's regions
    assert not [n for n in reduce + update if "jvp(" in n]
    assert not [n for n in reduce if UPDATE_SCOPE in n]
    # the scopes add names, not operations
    monkeypatch.setattr(jax, "named_scope",
                        contextlib.contextmanager(lambda name: (yield)))
    assert lowered().as_text() == step.as_text()


def test_already_reduced_gradients_are_counted_as_traced_collectives(
        world_mesh):
    # Under shard_map(check_vma=True) autodiff has psummed a replicated
    # parameter's gradient: C.allreduce is never called, the device still
    # runs an all-reduce, and hvt_traced_collectives_total must not read 0.
    from horovod_tpu import metrics
    from horovod_tpu.jax import allreduce_gradients
    from horovod_tpu.ops import collective_ops

    def per_shard(p, x):
        g = jax.grad(lambda p: jnp.mean((p["a"] * x[0] + p["b"]) ** 2))(p)
        return allreduce_gradients(g, axis_name=WORLD_AXIS)

    def count(op):
        return metrics.registry().get(
            "hvt_traced_collectives_total").labels(op=op).value

    collective_ops._metric_handles()    # makes the counter if nothing has
    before = count("allreduce_already_reduced"), count("allreduce")
    f = jax.jit(jax.shard_map(per_shard, mesh=world_mesh,
                              in_specs=(P(), P(WORLD_AXIS)), out_specs=P()))
    text = f.lower({"a": jnp.asarray(2.0), "b": jnp.asarray(0.5)},
                   jnp.ones((N, 4), jnp.float32)).as_text()
    assert "all_reduce" in text or "all-reduce" in text
    assert count("allreduce_already_reduced") == before[0] + 2   # a leaf each
    assert count("allreduce") == before[1]
