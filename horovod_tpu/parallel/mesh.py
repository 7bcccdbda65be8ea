"""Device meshes — the TPU-native communicator layer.

The reference maintains three communicators — GLOBAL, LOCAL (one node),
CROSS (one rank per node) — built at init (``horovod/common/common.h:115-119``,
``mpi_controller.cc:25-82``) and used by hierarchical collectives
(``ops/nccl_operations.cc:188-350``). On TPU the analog is a
:class:`jax.sharding.Mesh`:

- the **global mesh** is 1-D over every chip (axis ``hvt_world``) — GLOBAL;
- the **hierarchical mesh** is 2-D ``(hvt_cross, hvt_local)`` =
  (hosts, chips-per-host), so a ``psum`` over ``hvt_local`` rides ICI within
  a host and a ``psum`` over ``hvt_cross`` crosses DCN — exactly the
  reference's intra-node reduce-scatter / inter-node allreduce / intra-node
  allgather decomposition, except XLA emits and schedules the collectives.

``make_parallel_mesh`` builds general N-D meshes for dp/fsdp/pp/tp/sp/ep —
the parallelism strategies §2.6 of SURVEY.md marks absent in the reference
but which the TPU design gets from sharding annotations.
"""

from __future__ import annotations

import numpy as np

WORLD_AXIS = "hvt_world"
LOCAL_AXIS = "hvt_local"
CROSS_AXIS = "hvt_cross"

# Canonical parallelism axis names, outermost (most DCN-friendly) first.
# dp/fsdp change gradients (allreduce-heavy, tolerate DCN); tp/sp are
# latency-critical (keep on ICI, innermost).
PARALLEL_AXES = ("dp", "fsdp", "pp", "ep", "sp", "tp")

_global_mesh = None
_hier_mesh = None


def _jax():
    import jax

    return jax


def build_global_mesh():
    """(Re)build the global 1-D mesh over all chips. Called from hvt.init()."""
    global _global_mesh, _hier_mesh
    from horovod_tpu.metrics import startup

    jax = _jax()
    # a process's first jax.devices() brings the runtime up: on a TPU most
    # of what a start costs before the first program
    with startup.span("devices"):
        devices = np.asarray(jax.devices())
    _global_mesh = jax.sharding.Mesh(devices, axis_names=(WORLD_AXIS,))
    _hier_mesh = None
    return _global_mesh


def _reset():
    global _global_mesh, _hier_mesh
    _global_mesh = None
    _hier_mesh = None


def global_mesh():
    """The GLOBAL communicator: 1-D mesh, axis ``hvt_world``."""
    if _global_mesh is None:
        raise ValueError("horovod_tpu not initialized; call hvt.init() first")
    return _global_mesh


def hierarchical_mesh():
    """(hosts × chips-per-host) mesh — the LOCAL/CROSS communicator pair.

    Requires a homogeneous job (same chip count per host), like the
    reference's hierarchical ops (``operations.cc:472-480`` forces the
    hierarchical knobs off for inhomogeneous clusters).
    """
    global _hier_mesh
    if _hier_mesh is not None:
        return _hier_mesh
    jax = _jax()
    devices = jax.devices()
    by_proc = {}
    for d in devices:
        by_proc.setdefault(d.process_index, []).append(d)
    counts = {len(v) for v in by_proc.values()}
    if len(counts) != 1:
        raise ValueError(
            "hierarchical_mesh requires a homogeneous job "
            f"(chips per host: { {k: len(v) for k, v in by_proc.items()} })")
    rows = [sorted(v, key=lambda d: d.id)
            for _, v in sorted(by_proc.items())]
    arr = np.asarray(rows)  # [hosts, chips_per_host]
    _hier_mesh = jax.sharding.Mesh(arr, axis_names=(CROSS_AXIS, LOCAL_AXIS))
    return _hier_mesh


def make_parallel_mesh(devices=None, **axis_sizes):
    """Build an N-D mesh for arbitrary parallelism strategies.

    ``axis_sizes`` maps axis name → size; one axis may be ``-1`` to absorb
    the remaining devices. Axes are laid out in :data:`PARALLEL_AXES` order
    (unknown names keep their kwarg order, appended innermost) so that tp/sp
    land on the fastest (innermost, ICI-adjacent) mesh dimensions.

    Example::

        mesh = make_parallel_mesh(dp=-1, tp=4)          # e.g. (64, 4) on 256
        mesh = make_parallel_mesh(dp=2, sp=2, tp=2)     # 8 devices
    """
    jax = _jax()
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)

    names = [a for a in PARALLEL_AXES if a in axis_sizes]
    names += [a for a in axis_sizes if a not in names]
    sizes = [axis_sizes[a] for a in names]

    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if -1 in sizes:
        if n % fixed != 0:
            raise ValueError(
                f"{n} devices not divisible by fixed axes product {fixed}")
        sizes[sizes.index(-1)] = n // fixed
        fixed = n
    if fixed != n:
        raise ValueError(
            f"mesh axes {dict(zip(names, sizes))} use {fixed} devices, "
            f"but {n} are available")
    arr = np.asarray(devices).reshape(sizes)
    return jax.sharding.Mesh(arr, axis_names=tuple(names))
