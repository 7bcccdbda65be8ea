"""The Horovod spelling: per-chip gradients under ``jax.shard_map`` over
``hvt``'s global mesh, reduced by
``hvt.DistributedOptimizer(axis_name=WORLD_AXIS)`` — the package's own
reduction code in the compiled step."""

from __future__ import annotations

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu.parallel.mesh import WORLD_AXIS, global_mesh

from chipbench import compare
from chipbench.spellings import Spelled, has_all_reduce, replicas_identical

# Two reductions of the same float32 gradients, summed in another order:
# their norms agree to a few float32 roundings of a sum over 1e8 terms.
# A double reduction reads n times, a missing one sqrt(n) or so.
PROBE_NORM_REL_BOUND = 1e-3


def build(job, devices) -> Spelled:
    mesh = global_mesh()
    if set(mesh.devices.flat) != set(devices):
        raise ValueError(
            f"the shard_map spelling runs over hvt's global mesh "
            f"({mesh.devices.size} chips); the cell asks for "
            f"{len(devices)}")
    n = len(devices)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(WORLD_AXIS))
    tx = hvt.DistributedOptimizer(job.optimizer(), axis_name=WORLD_AXIS)

    def per_chip(params, extra, opt_state, batch):
        (loss, extra), grads = jax.value_and_grad(job.loss, has_aux=True)(
            params, extra, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), extra, opt_state,
                jax.lax.pmean(loss, WORLD_AXIS))

    step = jax.shard_map(per_chip, mesh=mesh,
                         in_specs=(P(), P(), P(), P(WORLD_AXIS)),
                         out_specs=(P(), P(), P(), P()))

    def verify_before(key):
        """The norm of the gradient as the package reduces it (through an
        ``sgd(1.0)`` probe, whose update is minus the reduced gradient)
        against the norm of a ``lax.pmean`` of the per-chip gradients
        written here. On the probe job: same widths and code, two
        gradient programs of the full depth would not fit beside it."""
        probe = job.probe
        k_init, k_batch = jax.random.split(key)
        params, extra = jax.jit(probe.init, out_shardings=replicated)(k_init)
        batch = jax.jit(lambda k: probe.make_batch(k, n),
                        out_shardings=sharded)(k_batch)
        probe_tx = hvt.DistributedOptimizer(optax.sgd(1.0),
                                            axis_name=WORLD_AXIS)

        def norms(params, extra, batch):
            loss = lambda p: probe.loss(p, extra, batch)[0]
            # the package's path: gradients of replicated parameters,
            # which autodiff under shard_map has already summed
            reduced, _ = probe_tx.update(jax.grad(loss)(params),
                                         probe_tx.init(params), params)
            # the plain path: make the parameters per-chip values first,
            # so that the gradients are this chip's alone, then average
            own = jax.grad(loss)(jax.lax.pcast(params, WORLD_AXIS,
                                               to="varying"))
            return (optax.global_norm(reduced),
                    optax.global_norm(jax.lax.pmean(own, WORLD_AXIS)))

        got, want = jax.jit(jax.shard_map(
            norms, mesh=mesh, in_specs=(P(), P(), P(WORLD_AXIS)),
            out_specs=(P(), P())))(params, extra, batch)
        return [compare.close("reduced_gradient_norm_vs_pmean", float(got),
                              float(want), PROBE_NORM_REL_BOUND)]

    return Spelled(
        state_sharding=replicated, batch_sharding=sharded, tx=tx, step=step,
        verify_before=verify_before,
        verify_compiled=lambda compiled: [has_all_reduce(compiled)],
        verify_after=lambda params: [
            replicas_identical(params, mesh, WORLD_AXIS)])
