"""horovod_tpu — a TPU-native distributed training framework.

Capability parity with Horovod (reference: aoyandong/horovod, see SURVEY.md),
re-designed for TPU hardware:

- Collectives lower to XLA ``AllReduce`` / ``ReduceScatter`` / ``AllGather`` /
  ``AllToAll`` / ``CollectivePermute`` over ICI (within a pod slice) and DCN
  (across hosts/slices), instead of NCCL/MPI verbs.
- The data-parallel training step is a single SPMD program compiled by XLA over
  a :class:`jax.sharding.Mesh`; gradient reduction is part of the program, so
  the reference's per-tensor readiness negotiation (rank-0 coordinator,
  ``controller.cc``) is only needed for the *eager* / cross-process path, which
  is served by a C++ core engine (``horovod_tpu/csrc``).
- One Python process per **host** drives all local chips (vs. the reference's
  one process per GPU); the Horovod GLOBAL/LOCAL/CROSS communicator triple
  (reference ``horovod/common/common.h:115-119``) maps to
  chips / chips-on-this-host / hosts.

Public API mirrors ``horovod.tensorflow`` / ``horovod.torch``
(reference ``horovod/tensorflow/__init__.py``, ``horovod/torch/__init__.py``):

    import horovod_tpu as hvt
    hvt.init()
    hvt.rank(), hvt.size(), hvt.local_rank(), hvt.local_size()
    hvt.allreduce(x), hvt.allgather(x), hvt.broadcast(x, root_rank=0)
    opt = hvt.DistributedOptimizer(optax.adam(1e-3))
"""

# first and last statement of this file: the ``import`` phase of a job's
# start (metrics/startup.py)
from horovod_tpu.metrics import startup as _startup
_import_span = _startup.span("import")
_import_span.__enter__()

from horovod_tpu.common.basics import (
    init,
    shutdown,
    is_initialized,
    start_timeline,
    stop_timeline,
    startup_report,
    diagnostics,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    process_rank,
    process_size,
    is_homogeneous,
    nccl_built,
    mpi_built,
    mpi_enabled,
    gloo_built,
    gloo_enabled,
    cuda_built,
    rocm_built,
    ccl_built,
    ddl_built,
    xla_built,
    mpi_threads_supported,
)
from horovod_tpu.common.exceptions import (
    HorovodInternalError,
    HorovodTimeoutError,
    HostsUpdatedInterrupt,
)
from horovod_tpu.common.process_sets import (
    ProcessSet,
    global_process_set,
    add_process_set,
    remove_process_set,
    process_set_included_ranks,
)
from horovod_tpu.ops.collective_ops import (
    allreduce,
    allreduce_async,
    grouped_allreduce,
    allgather,
    allgather_async,
    grouped_allgather,
    broadcast,
    broadcast_async,
    alltoall,
    alltoall_async,
    reducescatter,
    grouped_reducescatter,
    barrier,
    join,
    synchronize,
    poll,
    wire_compression,
    Average,
    Sum,
    Adasum,
    Min,
    Max,
    Product,
)
from horovod_tpu.ops.compression import Compression
from horovod_tpu.ops.functions import (
    allgather_object,
    broadcast_object,
    broadcast_parameters,
    broadcast_variables,
    broadcast_optimizer_state,
)
from horovod_tpu.jax import (
    DistributedOptimizer,
    DistributedGradientTransformation,
    PartialDistributedGradientTransformation,
)
from horovod_tpu import elastic

__version__ = "0.1.0"


def __getattr__(name):
    # lazy submodules: checkpoint pulls in orbax, runner pulls launcher
    # machinery — neither belongs in the base import path (metrics is
    # standard library only and is imported above, for the start's spans)
    if name in ("checkpoint", "runner"):
        import importlib

        return importlib.import_module(f"horovod_tpu.{name}")
    raise AttributeError(name)

__all__ = [
    # lifecycle
    "init", "shutdown", "is_initialized", "start_timeline", "stop_timeline",
    "startup_report", "diagnostics",
    # topology
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "process_rank", "process_size", "is_homogeneous",
    # build info (TPU build: these document what the backend is)
    "nccl_built", "mpi_built", "mpi_enabled", "gloo_built", "gloo_enabled",
    "cuda_built", "rocm_built", "ccl_built", "ddl_built", "xla_built",
    "mpi_threads_supported",
    # process sets
    "ProcessSet", "global_process_set", "add_process_set", "remove_process_set",
    "process_set_included_ranks",
    # collectives
    "allreduce", "allreduce_async", "grouped_allreduce",
    "allgather", "allgather_async", "grouped_allgather",
    "broadcast", "broadcast_async", "alltoall", "alltoall_async",
    "reducescatter", "grouped_reducescatter", "barrier", "join",
    "synchronize", "poll",
    "Average", "Sum", "Adasum", "Min", "Max", "Product",
    # helpers
    "Compression", "allgather_object", "broadcast_object",
    "broadcast_parameters", "broadcast_variables", "broadcast_optimizer_state",
    # optimizer
    "DistributedOptimizer", "DistributedGradientTransformation",
    "PartialDistributedGradientTransformation",
    # elastic
    "elastic",
    # telemetry
    "metrics",
    # exceptions
    "HorovodInternalError", "HorovodTimeoutError",
    "HostsUpdatedInterrupt",
]

_import_span.__exit__(None, None, None)
del _import_span
