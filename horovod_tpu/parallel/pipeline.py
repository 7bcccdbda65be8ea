"""Pipeline parallelism (PP) — GPipe-style microbatch pipelining over a
``pp`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.6); on TPU it
falls out of the SPMD building blocks: every stage runs the same compiled
program each tick, activations hop to the next stage with
``lax.ppermute`` over ICI, and the schedule is a ``lax.scan`` —
compiler-friendly control flow with static shapes, no host round-trips.

Schedule: B microbatches over S stages take B + S - 1 ticks. At tick t,
stage s computes microbatch ``t - s`` (a bubble when that index is out of
range — inherent to GPipe; keep B ≫ S to amortize). Stage boundaries are
neighbor exchanges on the ICI torus, so communication per tick is one
activation tensor per link.

Constraints of this formulation: every stage maps activations of one
uniform shape to the same shape (standard for transformer blocks).
Autodiff works through the whole schedule (``scan`` + ``ppermute`` are
differentiable), so ``jax.grad`` of a pipelined loss gives the 1F1B-less
GPipe backward for free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

PIPELINE_AXIS = "pp"


def split_microbatches(x, n_micro: int):
    """[batch, ...] → [n_micro, batch/n_micro, ...]."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def merge_microbatches(x):
    """Inverse of :func:`split_microbatches`."""
    return x.reshape((-1,) + x.shape[2:])


def pipeline_apply(stage_fn, stage_params, microbatches,
                   axis_name: str = PIPELINE_AXIS):
    """Run the pipeline; call INSIDE ``shard_map`` over ``axis_name``.

    - ``stage_fn(params, x) -> y`` with ``y.shape == x.shape``.
    - ``stage_params``: this stage's parameter pytree (leaves already
      sliced to the local stage, leading stage dim squeezed).
    - ``microbatches``: ``[n_micro, mb, ...]`` — the full input,
      replicated over the axis (only stage 0 reads it).

    Returns ``[n_micro, mb, ...]`` outputs, valid on the LAST stage
    (other stages hold zeros); wrap with :func:`pipeline` to get the
    result gathered to every shard.
    """
    S = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    ticks = n_micro + S - 1
    mb_shape = microbatches.shape[1:]

    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        recv, out_buf = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        first_stage_in = lax.dynamic_index_in_dim(
            microbatches, mb_idx, keepdims=False)
        inp = jnp.where(s == 0, first_stage_in, recv)
        act = stage_fn(stage_params, inp)
        sent = lax.ppermute(act, axis_name, fwd_perm)
        # last stage: act computed at tick t belongs to microbatch t-(S-1)
        out_idx = t - (S - 1)
        write = (s == S - 1) & (out_idx >= 0)
        out_buf = jnp.where(
            write,
            lax.dynamic_update_index_in_dim(
                out_buf, act, jnp.clip(out_idx, 0, n_micro - 1), 0),
            out_buf)
        return (sent, out_buf), None

    init = (jnp.zeros(mb_shape, microbatches.dtype),
            jnp.zeros((n_micro,) + mb_shape, microbatches.dtype))
    (_, out_buf), _ = lax.scan(tick, init, jnp.arange(ticks))
    return out_buf


def pipeline(stage_fn, stacked_params, x, n_micro: int, mesh,
             axis_name: str = PIPELINE_AXIS):
    """Convenience wrapper: shard stacked stage parameters over the pipe
    axis, run the schedule, return ``[batch, ...]`` outputs on every
    shard.

    ``stacked_params``: pytree with a leading stage dimension of size S
    on every leaf (the scan-over-layers layout).
    """

    def per_shard(params, xs):
        local = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        mb = split_microbatches(xs, n_micro)
        out = pipeline_apply(stage_fn, local, mb, axis_name=axis_name)
        # result lives on the last stage; a psum broadcasts it (all other
        # shards contribute zeros)
        out = lax.psum(out, axis_name)
        return merge_microbatches(out)

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params),
                  P()),
        out_specs=P(),
        check_vma=False)(stacked_params, x)


def stage_partition_spec(stacked_params, axis_name: str = PIPELINE_AXIS):
    """PartitionSpecs placing each leaf's leading stage dim on the pipe
    axis (for device_put before entering :func:`pipeline`)."""
    return jax.tree.map(
        lambda leaf: P(*((axis_name,) + (None,) * (leaf.ndim - 1))),
        stacked_params)
