"""Collective operations — allreduce / allgather / broadcast / alltoall /
reducescatter / join / barrier.

Parity surface: ``horovod/torch/mpi_ops.py`` + ``horovod/tensorflow/mpi_ops.py``
(reference anchors in each docstring). Two execution paths, chosen
automatically:

**Traced path** (inside ``jit``/``shard_map``/``pmap``, tensor is a tracer):
the collective is emitted *into* the XLA program as a native ICI collective
(``lax.psum``/``all_gather``/``psum_scatter``/``all_to_all``). The reference's
background engine exists to discover, across independent processes, which
tensors are globally ready and to fuse them (``controller.cc:69``,
``FuseResponses:777``); inside a single compiled SPMD program both concerns
vanish — every shard reaches the collective at the same program point, and
XLA's scheduler fuses/overlaps collectives with compute. This is the hot path
for TPU training and the reason the TPU design needs no per-step negotiation.

**Eager path** (numpy arrays, concrete jax Arrays, Python scalars): one
contribution per *process*, reduced across processes over DCN by the C++
engine (``horovod_tpu/engine``) — the analog of the reference's
enqueue/negotiate/execute pipeline (``operations.cc:900-1188``). Used for
metrics averaging, parameter broadcast, object collectives, and the
PyTorch-style eager workflow.

Async semantics mirror the reference: ``*_async`` returns a handle;
``synchronize(handle)`` blocks (``torch/mpi_ops.py:823``); ``poll(handle)``
tests completion (``torch/mpi_ops.py:807``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.process_sets import ProcessSet, global_process_set
from horovod_tpu.parallel import mesh as _mesh_mod


class ReduceOp:
    """Reduction op constants (reference ``horovod/torch/mpi_ops.py:48-56``)."""

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"hvt.{self.name}"


Average = ReduceOp("Average")
Sum = ReduceOp("Sum")
Adasum = ReduceOp("Adasum")
Min = ReduceOp("Min")
Max = ReduceOp("Max")
Product = ReduceOp("Product")


def _is_traced(x) -> bool:
    return any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves(x))


# --------------------------------------------------------------------------
# dispatch telemetry (horovod_tpu.metrics)
# --------------------------------------------------------------------------
# Eager dispatches get a wall-clock latency histogram and a byte counter
# per (op, process_set); traced dispatches are counted at TRACE time only
# (the collective then lives inside the compiled program, invisible to
# Python — per-execution device timing belongs to the XLA profiler).
# Metric handles are cached so the per-call cost is a dict lookup + one
# histogram observe (~1 µs; pinned by tests/test_metrics.py).

_dispatch_metrics = None


def _metric_handles():
    global _dispatch_metrics
    if _dispatch_metrics is None:
        from horovod_tpu import metrics as _metrics

        _dispatch_metrics = (
            _metrics.histogram(
                "hvt_collective_latency_seconds",
                "eager collective wall-clock latency (dispatch to "
                "completion)", ("op", "process_set")),
            _metrics.counter(
                "hvt_collective_bytes_total",
                "payload bytes submitted to eager collectives",
                ("op", "process_set")),
            _metrics.counter(
                "hvt_traced_collectives_total",
                "collectives emitted into compiled XLA programs "
                "(counted per trace, not per execution)", ("op",)),
        )
    return _dispatch_metrics


def _ps_label(process_set) -> str:
    ranks = getattr(process_set, "ranks", None) if process_set else None
    if ranks is None:
        return "global"
    return ",".join(str(r) for r in sorted(int(r) for r in ranks))


def _payload_bytes(tensor) -> int:
    total = 0
    for leaf in jax.tree.leaves(tensor):
        nb = getattr(leaf, "nbytes", None)
        total += int(nb) if nb is not None else 0
    return total


def _count_traced(op_name: str):
    try:
        _metric_handles()[2].labels(op=op_name).inc()
    except Exception:
        pass  # telemetry must never break a dispatch


def _timed_eager(op_name: str, process_set, tensor, fn):
    """Run ``fn()`` (the eager submit+synchronize path) under the
    dispatch histogram/byte counter."""
    hist, bytes_total, _ = _metric_handles()
    ps = _ps_label(process_set)
    bytes_total.labels(op=op_name, process_set=ps).inc(
        _payload_bytes(tensor))
    t0 = time.monotonic()
    try:
        # a host span on the profiler's clock, the one a device trace
        # shares (docs/timeline.md); costs nothing while no trace runs
        with jax.profiler.TraceAnnotation(f"hvt_eager/{op_name}"):
            return fn()
    finally:
        hist.labels(op=op_name, process_set=ps).observe(
            time.monotonic() - t0)


def _resolve_op(op, average):
    """Reference keeps deprecated ``average=`` alongside ``op=``
    (``torch/mpi_ops.py:85-129``)."""
    if op is not None and average is not None:
        raise ValueError("specify either op= or average=, not both")
    if op is None:
        if average is None or average:
            return Average
        return Sum
    return op


def _axis_or_default(axis_name):
    return axis_name if axis_name is not None else _mesh_mod.WORLD_AXIS


def _groups(process_set: ProcessSet, axis_name):
    if process_set is None or process_set.ranks is None:
        return None
    world = _axis_world_size(axis_name)
    return process_set.axis_index_groups(world)


def _axis_world_size(axis_name):
    return lax.axis_size(axis_name)


def _equal_groups(process_set: ProcessSet, axis_name, op_name):
    """Replica groups + group size for shape-changing collectives
    (allgather / alltoall / reducescatter).

    XLA requires replica groups to partition the axis into EQUAL-size
    groups for these ops (the output shape depends on group size). A
    process set whose complement has a different size cannot be lowered;
    raise an actionable error instead of XLA's 'Invalid replica id -1'.
    """
    if process_set is None or process_set.ranks is None:
        return None, _axis_world_size(axis_name)
    world = _axis_world_size(axis_name)
    groups = process_set.axis_index_groups(world)
    if groups is None:
        return None, world
    sizes = {len(g) for g in groups}
    if len(sizes) != 1:
        raise ValueError(
            f"traced {op_name} over a process set requires the set and its "
            f"complement to have equal sizes (XLA replica groups must "
            f"partition the axis evenly); got sizes "
            f"{sorted(len(g) for g in groups)}. Use the eager path or a "
            f"set of size {world // 2}.")
    return groups, sizes.pop()


# --------------------------------------------------------------------------
# allreduce
# --------------------------------------------------------------------------

def allreduce(tensor, average=None, name=None, op=None,
              prescale_factor=1.0, postscale_factor=1.0,
              process_set=global_process_set, axis_name=None):
    """Reduce ``tensor`` across workers.

    Traced: emits an XLA AllReduce over the mesh axis ``axis_name``
    (default ``hvt_world``). Eager: engine collective across processes.
    Reference: ``horovod/torch/mpi_ops.py:223`` / ``operations.cc:929``
    (pre/postscale handling at ``operations.cc:941-957``).
    """
    if _is_traced(tensor):
        _count_traced("allreduce")
        return jax.tree.map(
            lambda t: _traced_allreduce(
                t, _resolve_op(op, average), _axis_or_default(axis_name),
                process_set, prescale_factor, postscale_factor),
            tensor)
    return _timed_eager(
        "allreduce", process_set, tensor,
        lambda: synchronize(allreduce_async(
            tensor, average=average, name=name, op=op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            process_set=process_set)))


def grouped_reduce(t, op, axis, groups):
    """Reduce within replica groups.

    Native ``axis_index_groups`` is used when the installed jax supports it
    under shard_map's varying-axes checking; otherwise fall back to one
    masked full-axis reduce per group (process sets are usually
    set+complement, so 2 reduces) selected by membership — semantically
    identical, costs an extra full-axis pass.
    """
    native = {Average: lax.pmean, Sum: lax.psum, Min: lax.pmin,
              Max: lax.pmax}[op]
    if groups is None:
        return native(t, axis)
    try:
        return native(t, axis, axis_index_groups=groups)
    except NotImplementedError:
        pass
    idx = lax.axis_index(axis)
    identity = {
        Sum: jnp.zeros((), t.dtype),
        Average: jnp.zeros((), t.dtype),
        Min: jnp.asarray(jnp.finfo(t.dtype).max
                         if jnp.issubdtype(t.dtype, jnp.floating)
                         else jnp.iinfo(t.dtype).max, t.dtype),
        Max: jnp.asarray(jnp.finfo(t.dtype).min
                         if jnp.issubdtype(t.dtype, jnp.floating)
                         else jnp.iinfo(t.dtype).min, t.dtype),
    }[op]
    base = {Average: lax.psum, Sum: lax.psum, Min: lax.pmin,
            Max: lax.pmax}[op]
    out = jnp.full_like(t, identity)
    # singletons reduce to themselves — no collective needed (adasum
    # pairing emits one singleton per finished/complement rank, which
    # would otherwise cost O(n) full-axis reduces here)
    singles = [g[0] for g in groups if len(g) == 1]
    if singles:
        out = jnp.where(jnp.isin(idx, jnp.asarray(singles)), t, out)
    for g in groups:
        if len(g) == 1:
            continue
        member = jnp.isin(idx, jnp.asarray(g))
        contrib = jnp.where(member, t, identity)
        red = base(contrib, axis)
        if op is Average:
            red = red / len(g)
        out = jnp.where(member, red, out)
    return out


def _traced_allreduce(t, op, axis, process_set, prescale, postscale):
    groups = _groups(process_set, axis)
    if prescale != 1.0:
        t = t * jnp.asarray(prescale, t.dtype)
    if op in (Average, Sum, Min, Max):
        r = grouped_reduce(t, op, axis, groups)
    elif op is Product:
        # No native pprod collective; product = exp(psum(log)) is unstable,
        # so gather the factors and multiply.
        g = lax.all_gather(t, axis, axis_index_groups=groups)
        r = jnp.prod(g, axis=0)
    elif op is Adasum:
        from horovod_tpu.ops import adasum as _adasum

        if groups is not None:
            # ProcessSet groups are [set, complement]; the complement must
            # pass through unchanged (and may not be power-of-two sized),
            # so it participates as singletons
            members, rest = groups[0], [r for g in groups[1:] for r in g]
            groups = [list(members)] + [[r] for r in rest]
        r = _adasum.adasum_reduce(t, axis, axis_index_groups=groups)
    else:
        raise ValueError(f"unknown reduce op {op}")
    if postscale != 1.0:
        r = r * jnp.asarray(postscale, r.dtype)
    return r


def allreduce_async(tensor, average=None, name=None, op=None,
                    prescale_factor=1.0, postscale_factor=1.0,
                    process_set=global_process_set):
    """Eager async allreduce → handle (``torch/mpi_ops.py:130``)."""
    if _is_traced(tensor):
        raise ValueError(
            "allreduce_async is the eager API; inside jit use hvt.allreduce "
            "(the collective is part of the program and already async under "
            "XLA's scheduler)")
    from horovod_tpu.engine import api as engine

    return engine.allreduce(tensor, op=_resolve_op(op, average), name=name,
                            prescale_factor=prescale_factor,
                            postscale_factor=postscale_factor,
                            process_set=process_set)


def grouped_allreduce(tensors, average=None, name=None, op=None,
                      prescale_factor=1.0, postscale_factor=1.0,
                      process_set=global_process_set, axis_name=None):
    """Reduce a list of tensors as one fused unit.

    Reference: ``EnqueueTensorAllreduces`` (``operations.cc:929``) +
    GroupTable deterministic fusion. Traced: emitting the psums adjacent in
    one program lets XLA's collective combiner fuse them (the compiler plays
    the role of ``FuseResponses``, ``controller.cc:777``). Eager: the engine
    negotiates them as one group.
    """
    if _is_traced(tensors):
        return [allreduce(t, average=average, op=op,
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor,
                          process_set=process_set, axis_name=axis_name)
                for t in tensors]
    from horovod_tpu.engine import api as engine

    def _run():
        h = engine.grouped_allreduce(
            tensors, op=_resolve_op(op, average), name=name,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=process_set)
        return synchronize(h)

    return _timed_eager("grouped_allreduce", process_set, tensors, _run)


def grouped_allreduce_async(tensors, average=None, name=None, op=None,
                            prescale_factor=1.0, postscale_factor=1.0,
                            process_set=global_process_set):
    from horovod_tpu.engine import api as engine

    return engine.grouped_allreduce(tensors, op=_resolve_op(op, average),
                                    name=name,
                                    prescale_factor=prescale_factor,
                                    postscale_factor=postscale_factor,
                                    process_set=process_set)


# --------------------------------------------------------------------------
# allgather
# --------------------------------------------------------------------------

def allgather(tensor, name=None, process_set=global_process_set,
              axis_name=None):
    """Concatenate ``tensor`` from all workers along dim 0.

    Traced: XLA AllGather (equal shard shapes — XLA is statically shaped).
    Eager: engine allgatherv, which supports different dim-0 sizes per
    process like the reference (``collective_operations.h:140-176``).
    Reference API: ``torch/mpi_ops.py:502``.
    """
    if _is_traced(tensor):
        _count_traced("allgather")
        axis = _axis_or_default(axis_name)
        groups, _ = _equal_groups(process_set, axis, "allgather")
        return jax.tree.map(
            lambda t: lax.all_gather(t, axis, axis_index_groups=groups,
                                     tiled=True),
            tensor)
    return _timed_eager(
        "allgather", process_set, tensor,
        lambda: synchronize(allgather_async(tensor, name=name,
                                            process_set=process_set)))


def allgather_async(tensor, name=None, process_set=global_process_set):
    from horovod_tpu.engine import api as engine

    return engine.allgather(tensor, name=name, process_set=process_set)


def grouped_allgather(tensors, name=None, process_set=global_process_set,
                      axis_name=None):
    if _is_traced(tensors):
        return [allgather(t, process_set=process_set, axis_name=axis_name)
                for t in tensors]
    from horovod_tpu.engine import api as engine

    return synchronize(engine.grouped_allgather(tensors, name=name,
                                                process_set=process_set))


# --------------------------------------------------------------------------
# broadcast
# --------------------------------------------------------------------------

def broadcast(tensor, root_rank=0, name=None,
              process_set=global_process_set, axis_name=None):
    """Broadcast ``tensor`` from ``root_rank`` to all workers.

    Traced: implemented as a masked AllReduce (zero everywhere but the root,
    then psum) — one ICI allreduce, same bandwidth class as XLA's own
    broadcast lowering, no n× gather buffer. Eager: engine broadcast.
    Reference API: ``torch/mpi_ops.py:585`` / ``operations.cc:1060``.
    """
    if _is_traced(tensor):
        _count_traced("broadcast")
        axis = _axis_or_default(axis_name)
        groups = _groups(process_set, axis)

        def _bcast(t):
            idx = lax.axis_index(axis)
            masked = jnp.where(idx == root_rank, t,
                               jnp.zeros_like(t))
            return lax.psum(masked, axis, axis_index_groups=groups)

        return jax.tree.map(_bcast, tensor)
    return _timed_eager(
        "broadcast", process_set, tensor,
        lambda: synchronize(broadcast_async(tensor, root_rank=root_rank,
                                            name=name,
                                            process_set=process_set)))


def broadcast_async(tensor, root_rank=0, name=None,
                    process_set=global_process_set):
    from horovod_tpu.engine import api as engine

    return engine.broadcast(tensor, root_rank=root_rank, name=name,
                            process_set=process_set)


# --------------------------------------------------------------------------
# alltoall
# --------------------------------------------------------------------------

def alltoall(tensor, splits=None, name=None,
             process_set=global_process_set, axis_name=None):
    """Scatter dim-0 slices of ``tensor`` to all workers and gather what they
    sent back — the EP / sequence-exchange primitive.

    Traced: even splits lower to one XLA AllToAll; uneven (static) splits are
    not expressible with static shapes, use the eager/engine path or pad.
    Eager: engine alltoallv with per-process splits and received-splits
    return, matching ``operations.cc:1099-1160``.
    Reference API: ``torch/mpi_ops.py:710``.
    """
    if _is_traced(tensor):
        if splits is not None:
            raise ValueError(
                "uneven alltoall splits are not representable in a "
                "statically-shaped XLA program; pad to even splits or use "
                "the eager path")
        _count_traced("alltoall")
        axis = _axis_or_default(axis_name)
        groups, group_size = _equal_groups(process_set, axis, "alltoall")

        def _a2a(t):
            if t.shape[0] % group_size != 0:
                raise ValueError(
                    f"alltoall dim 0 ({t.shape[0]}) must divide the group "
                    f"size ({group_size}) for the traced path")
            return lax.all_to_all(t, axis, split_axis=0, concat_axis=0,
                                  tiled=True, axis_index_groups=groups)

        return jax.tree.map(_a2a, tensor)
    return _timed_eager(
        "alltoall", process_set, tensor,
        lambda: synchronize(alltoall_async(tensor, splits=splits,
                                           name=name,
                                           process_set=process_set)))


def alltoall_async(tensor, splits=None, name=None,
                   process_set=global_process_set):
    from horovod_tpu.engine import api as engine

    return engine.alltoall(tensor, splits=splits, name=name,
                           process_set=process_set)


# --------------------------------------------------------------------------
# reducescatter
# --------------------------------------------------------------------------

def reducescatter(tensor, op=None, name=None,
                  process_set=global_process_set, axis_name=None,
                  prescale_factor=1.0, postscale_factor=1.0):
    """Reduce across workers, scatter dim-0 slices — the building block of
    hierarchical and bandwidth-optimal allreduce
    (``nccl_operations.cc:188-350`` uses ReduceScatter+AllGather).

    Traced: ``lax.psum_scatter``. Average divides by the reducing group's
    size after the sum, matching the reference's postscale convention.
    """
    rop = op if op is not None else Average
    if _is_traced(tensor):
        _count_traced("reducescatter")
        axis = _axis_or_default(axis_name)
        groups, group_size = _equal_groups(process_set, axis,
                                           "reducescatter")

        def _rs(t):
            if t.shape[0] % group_size != 0:
                raise ValueError(
                    f"reducescatter dim 0 ({t.shape[0]}) must divide the "
                    f"group size ({group_size}) for the traced path")
            if prescale_factor != 1.0:
                t2 = t * jnp.asarray(prescale_factor, t.dtype)
            else:
                t2 = t
            r = lax.psum_scatter(t2, axis, scatter_dimension=0, tiled=True,
                                 axis_index_groups=groups)
            if rop is Average:
                r = r / group_size
            post = postscale_factor
            if post != 1.0:
                r = r * jnp.asarray(post, r.dtype)
            return r

        return jax.tree.map(_rs, tensor)
    from horovod_tpu.engine import api as engine

    return _timed_eager(
        "reducescatter", process_set, tensor,
        lambda: synchronize(engine.reducescatter(
            tensor, op=rop, name=name, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, process_set=process_set)))


def grouped_reducescatter(tensors, op=None, name=None,
                          process_set=global_process_set, axis_name=None):
    return [reducescatter(t, op=op, process_set=process_set,
                          axis_name=axis_name) for t in tensors]


# --------------------------------------------------------------------------
# join / barrier / handles
# --------------------------------------------------------------------------

def join(device=None) -> int:
    """Signal that this process has exhausted its data; pending collectives
    proceed with zero stand-ins from joined ranks. Returns the last rank to
    join, so every worker can e.g. broadcast final state from it.

    Reference: ``EnqueueJoin`` (``operations.cc:1164``), ``JoinOp``
    (``collective_operations.h:259``). Eager/engine-path only: a compiled
    SPMD program cannot have ragged participation — on TPU uneven data is
    handled at the input pipeline (see ``horovod_tpu/data``), which pads or
    drops to keep every chip stepping together.
    """
    from horovod_tpu.engine import api as engine

    return _timed_eager("join", None, None, engine.join)


def barrier(process_set=global_process_set):
    """Block until all processes reach the barrier (engine control plane)."""
    from horovod_tpu.engine import api as engine

    return _timed_eager("barrier", process_set, None,
                        lambda: engine.barrier(process_set=process_set))


def synchronize(handle, timeout=None):
    """Block until an async handle completes; returns its output
    (``torch/mpi_ops.py:823``). Raises HorovodInternalError on engine
    failure — bounded by the engine's containment deadlines, never a
    hang — which elastic training interprets as a peer loss. With
    ``timeout`` (seconds), raises :class:`hvt.HorovodTimeoutError` if
    still pending at the deadline; the handle stays waitable."""
    return handle.wait(timeout=timeout)


def poll(handle) -> bool:
    """True if the async op has completed (``torch/mpi_ops.py:807``)."""
    return handle.done()


def wire_compression() -> tuple:
    """Current wire-codec pair of the eager data plane as
    ``(intra, inter)`` codec names — which codec intra-host links and
    cross-host links move (``"none"``, ``"bf16"``, ``"int8"`` or
    ``"fp8"``; the ``horovod_tpu.compression`` registry). E.g.
    ``("none", "int8")`` under ``HVT_WIRE_COMPRESSION=none,int8``
    (EQuARX-style: only the DCN hops quantize), ``("bf16", "bf16")``
    under the single-token form, ``("none", "none")`` by default.
    Under ``auto`` the pair reflects rank 0's latest tuner picks
    (``horovod_tpu.compression.auto_active()`` tells). Rank 0's
    setting governs the gang — the pair is stamped into every
    coordinated response, so mixed environments still agree on
    transfer sizes; ``hvt.diagnostics()`` / ``GET /debugz`` show each
    rank's view when debugging a mixed-codec gang. Distinct from
    ``hvt.Compression`` (framework-level cast before submission):
    wire codecs are transparent to callers and exist only on the TCP
    links, with per-tensor error feedback compensating the
    quantization (``HVT_ERROR_FEEDBACK``)."""
    from horovod_tpu import compression as _compression

    return _compression.wire_pair()
