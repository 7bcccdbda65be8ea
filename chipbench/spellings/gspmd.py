"""The GSPMD spelling: one ``jax.jit`` over a ``dp`` mesh of the cell's
chips, batch sharded over ``dp``, state replicated, and
``hvt.DistributedOptimizer(axis_name=None)`` because XLA's partitioner
inserts the gradient reduction itself. On one chip this is the plain
jitted step of ``examples/jax/``."""

from __future__ import annotations

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvt
from horovod_tpu.parallel.mesh import make_parallel_mesh

from chipbench.spellings import Spelled, has_all_reduce, replicas_identical


def build(job, devices) -> Spelled:
    mesh = make_parallel_mesh(devices=devices, dp=len(devices))
    tx = hvt.DistributedOptimizer(job.optimizer(), axis_name=None)

    def step(params, extra, opt_state, batch):
        (loss, extra), grads = jax.value_and_grad(job.loss, has_aux=True)(
            params, extra, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), extra, opt_state, loss

    several = len(devices) > 1
    return Spelled(
        state_sharding=NamedSharding(mesh, P()),
        batch_sharding=NamedSharding(mesh, P("dp")),
        tx=tx, step=step,
        verify_before=lambda key: [],
        verify_compiled=lambda compiled: [has_all_reduce(compiled)]
        if several else [],
        verify_after=lambda params: [replicas_identical(params, mesh, "dp")]
        if several else [])
