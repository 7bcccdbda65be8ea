"""JAX framework binding — the TPU-native analog of ``horovod.torch`` /
``horovod.tensorflow``'s optimizer layer.

``DistributedOptimizer`` wraps any optax ``GradientTransformation`` so that
gradients are combined across workers before being applied — the exact role
of ``hvd.DistributedOptimizer`` (reference ``tensorflow/__init__.py:568``,
``torch/optimizer.py:441``), with the same knobs: op (Average/Sum/Adasum),
compression, pre/postscale, ``gradient_predivide_factor``,
``backward_passes_per_step`` local aggregation
(``tensorflow/gradient_aggregation.py:16``, ``torch/optimizer.py:170-198``).

Where the reductions happen, TPU-natively:

- **shard_map / pmap training loops** (explicit per-chip gradients): pass
  ``axis_name=...`` and the wrapper emits ICI collectives into the step.
- **pjit global-array data parallelism**: XLA's autodiff of a
  batch-sharded loss already inserts the gradient ``psum`` (the compiler
  plays the role of Horovod's background engine). The wrapper then runs
  with ``axis_name=None`` (no second reduction) and still provides
  compression/aggregation/Adasum semantics.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.ops import collective_ops as C
from horovod_tpu.ops.compression import Compression

# The package's contract with any reader of a device trace: inside a compiled
# step, every operation of the gradient reduction (divides, casts, scaling,
# the collectives) carries REDUCE_SCOPE in its op_name, and every operation
# of the wrapped optimizer's update carries UPDATE_SCOPE. Trace-time metadata
# only: no operation is added. chipbench/regions.py reads both.
REDUCE_SCOPE = "hvt_reduce_gradients"
UPDATE_SCOPE = "hvt_optimizer_update"


def allreduce_gradients(grads, *, op=C.Average, axis_name=None,
                        compression=Compression.none,
                        prescale_factor=1.0, postscale_factor=1.0,
                        process_set=C.global_process_set):
    """Reduce a gradient pytree across workers (the body of
    ``_make_allreduce_grads_fn``, reference ``tensorflow/__init__.py:333``).

    ``axis_name=None`` means "already reduced by XLA sharding" and applies
    only the local transforms (compression round-trip, scaling).

    Varying-manual-axes subtlety: under ``shard_map(..., check_vma=True)``
    (the default), JAX's autodiff transpose *already* psums gradients of
    axis-invariant (replicated) parameters — the compiler inserted the
    allreduce for us. Such leaves arrive invariant over ``axis_name`` and
    hold the global **sum**; emitting another collective would be wrong, so
    for Average we only divide by the axis size. Per-shard (varying) leaves
    — including everything under ``check_vma=False`` — get the explicit
    collective.
    """

    def _already_reduced(leaf) -> bool:
        # axis_index is varying over its axis by construction, so its
        # type says whether the enclosing shard_map tracks varying axes
        # at all (check_vma=False and pmap leave every vma empty). No
        # fallback: a probe that cannot answer must raise, because a
        # wrong "not reduced" sums every replicated gradient twice.
        if axis_name not in jax.typeof(jax.lax.axis_index(axis_name)).vma:
            return False
        return axis_name not in jax.typeof(leaf).vma

    def _one(g):
        c, ctx = compression.compress(g)
        if axis_name is not None:
            if isinstance(c, jax.core.Tracer) and _already_reduced(c):
                # autodiff's own psum is in the program all the same: an
                # operator counting traced collectives must see it
                C._count_traced("allreduce_already_reduced")
                if op is C.Average:
                    c = c / jax.lax.axis_size(axis_name)
                if prescale_factor != 1.0:
                    c = c * jnp.asarray(prescale_factor, c.dtype)
                if postscale_factor != 1.0:
                    c = c * jnp.asarray(postscale_factor, c.dtype)
            else:
                c = C.allreduce(c, op=op, axis_name=axis_name,
                                prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                process_set=process_set)
        else:
            if prescale_factor != 1.0:
                c = c * jnp.asarray(prescale_factor, c.dtype)
            if postscale_factor != 1.0:
                c = c * jnp.asarray(postscale_factor, c.dtype)
        return compression.decompress(c, ctx)

    return jax.tree.map(_one, grads)


class _AggregationState(NamedTuple):
    """State for backward_passes_per_step local aggregation."""

    step: jnp.ndarray           # int32 counter
    acc: optax.Updates          # gradient accumulator
    inner_state: optax.OptState


def DistributedGradientTransformation(
        optimizer: optax.GradientTransformation,
        *,
        op=C.Average,
        axis_name: Optional[str] = None,
        compression=Compression.none,
        prescale_factor: float = 1.0,
        postscale_factor: float = 1.0,
        gradient_predivide_factor: float = 1.0,
        backward_passes_per_step: int = 1,
        average_aggregated_gradients: bool = False,
        num_groups: int = 0,
        process_set=C.global_process_set,
        reduce_filter: Optional[Callable[[tuple], bool]] = None,
) -> optax.GradientTransformation:
    """optax transformation: [accumulate N steps] → allreduce → inner update.

    Mirrors the reference semantics:

    - ``gradient_predivide_factor`` splits Average's 1/size between a
      prescale (f/size) and postscale (1/f), reference
      ``tensorflow/__init__.py:578-590``.
    - ``backward_passes_per_step > 1`` accumulates locally and performs the
      collective + inner update every Nth call; in-between calls return
      zero updates and leave the inner optimizer state untouched
      (``gradient_aggregation.py:16``; implemented with ``lax.cond`` so it
      stays a single compiled program).
    - ``average_aggregated_gradients`` divides the accumulator by N before
      reducing (``gradient_aggregation.py`` allreduce_grads path).
    - ``num_groups`` is accepted for parity; on the traced path XLA's
      collective combiner performs fusion, so the hint is a no-op.
    - under an explicit ``shard_map`` training loop,
      ``backward_passes_per_step > 1`` requires ``check_vma=False`` on the
      shard_map (the held/emit ``lax.cond`` mixes axis-varying and
      axis-invariant values, which the varying-manual-axes type checker
      can't yet express); ``jit``/pjit loops (``axis_name=None``) have no
      such restriction.
    - ``reduce_filter`` (TPU extension): predicate on the leaf path; leaves
      where it returns False skip the collective (stay process-local).
    """
    del num_groups
    if gradient_predivide_factor != 1.0:
        if op is not C.Average:
            raise ValueError(
                "gradient_predivide_factor requires op=Average "
                "(reference tensorflow/__init__.py:585)")
        # Average = Sum with pre/post scales (reference splits it this way).
        op = C.Sum
        prescale_factor = prescale_factor * gradient_predivide_factor
        postscale_factor = postscale_factor / gradient_predivide_factor
        _predivide_by_size = True
    else:
        _predivide_by_size = False

    @jax.named_scope(REDUCE_SCOPE)
    def _reduce(grads):
        pre, post = prescale_factor, postscale_factor
        if _predivide_by_size:
            if axis_name is not None:
                n = jax.lax.axis_size(axis_name)
            else:
                n = 1
            pre = pre / n
        if reduce_filter is None:
            return allreduce_gradients(
                grads, op=op, axis_name=axis_name, compression=compression,
                prescale_factor=pre, postscale_factor=post,
                process_set=process_set)
        flat = jax.tree_util.tree_flatten_with_path(grads)
        paths_leaves, treedef = flat
        out = []
        for path, leaf in paths_leaves:
            if reduce_filter(path):
                out.append(allreduce_gradients(
                    leaf, op=op, axis_name=axis_name,
                    compression=compression, prescale_factor=pre,
                    postscale_factor=post, process_set=process_set))
            else:
                out.append(leaf)
        return jax.tree.unflatten(treedef, out)

    _inner_update = jax.named_scope(UPDATE_SCOPE)(optimizer.update)

    if backward_passes_per_step == 1:
        def init(params):
            return optimizer.init(params)

        def update(grads, state, params=None, **extra):
            return _inner_update(_reduce(grads), state, params, **extra)

        return optax.GradientTransformation(init, update)

    n_steps = backward_passes_per_step

    def init(params):
        return _AggregationState(
            step=jnp.zeros((), jnp.int32),
            acc=jax.tree.map(jnp.zeros_like, params),
            inner_state=optimizer.init(params),
        )

    def update(grads, state, params=None, **extra):
        acc = jax.tree.map(jnp.add, state.acc, grads)
        emit = (state.step + 1) % n_steps == 0

        def do_emit(operand):
            acc_, inner_ = operand
            g = acc_
            if average_aggregated_gradients:
                g = jax.tree.map(lambda x: x / n_steps, g)
            g = _reduce(g)
            updates, new_inner = _inner_update(g, inner_, params, **extra)
            return updates, new_inner, jax.tree.map(jnp.zeros_like, acc_)

        def hold(operand):
            acc_, inner_ = operand
            zeros = jax.tree.map(jnp.zeros_like, acc_)
            return zeros, inner_, acc_

        updates, new_inner, new_acc = jax.lax.cond(
            emit, do_emit, hold, (acc, state.inner_state))
        return updates, _AggregationState(step=state.step + 1, acc=new_acc,
                                          inner_state=new_inner)

    return optax.GradientTransformation(init, update)


# The user-facing name, matching hvd.DistributedOptimizer.
DistributedOptimizer = DistributedGradientTransformation


from horovod_tpu.jax.callbacks import (  # noqa: E402,F401
    BroadcastGlobalVariablesCallback, Callback, CallbackList,
    LearningRateScheduleCallback, LearningRateWarmupCallback,
    MetricAverageCallback, MetricsCallback, exponential_schedule,
    warmup_schedule)


def __getattr__(name):
    # lazy: sync_batch_norm imports flax, which must stay an optional
    # dependency of `import horovod_tpu`
    if name == "SyncBatchNorm":
        from horovod_tpu.jax.sync_batch_norm import SyncBatchNorm

        return SyncBatchNorm
    raise AttributeError(name)


def PartialDistributedGradientTransformation(
        optimizer: optax.GradientTransformation,
        local_layers=(),
        **kwargs) -> optax.GradientTransformation:
    """Like DistributedOptimizer but leaves parameters whose path mentions a
    name in ``local_layers`` un-reduced (process-local parameters, e.g.
    per-host embeddings). Parity with the reference lineage's
    PartialDistributedOptimizer concept."""
    names = tuple(local_layers)

    def _filter(path) -> bool:
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        return not any(n in keys for n in names)

    return DistributedGradientTransformation(
        optimizer, reduce_filter=_filter, **kwargs)
