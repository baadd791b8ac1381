"""horovod_tpu: a TPU-native distributed training framework.

A ground-up rebuild of the capability surface of Horovod (reference:
``streichler/horovod``; see SURVEY.md) designed for TPU hardware: the data
plane is jit-compiled XLA collectives over ICI/DCN on ``jax.sharding``
meshes instead of NCCL/MPI streams; the control plane (async handles,
tensor fusion, response cache, timeline, stall detection, autotune,
elastic membership) is rebuilt natively on top of that substrate.

Quick start (data-parallel training, the reference's core use case)::

    import horovod_tpu as hvd

    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    # inside your jit'd step over the worker mesh, gradients are
    # bucket-fused and all-reduced over ICI automatically.
"""

import time as _time

_IMPORT_T0 = _time.monotonic()  # the ``import`` span opens here, closes below

from .version import __version__  # noqa: F401

# --- core runtime (reference: horovod/common/basics.py) ---------------------
from .runtime import (  # noqa: F401
    init, shutdown, is_initialized,
    rank, size, local_rank, local_size, cross_rank, cross_size,
    process_count, process_index, is_homogeneous,
    mesh, worker_axis,
    mpi_threads_supported, mpi_enabled, mpi_built, gloo_enabled, gloo_built,
    nccl_built, ddl_built, ccl_built, cuda_built, rocm_built, xla_built,
    tpu_built,
    start_timeline, stop_timeline, start_profiler, stop_profiler,
    ProcessSet, add_process_set, remove_process_set,
    get_process_set_ids_and_ranks,
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
)

# --- collective ops (reference: horovod/torch/mpi_ops.py) -------------------
from .api import (  # noqa: F401
    allreduce, allreduce_async, allreduce_, allreduce_async_,
    grouped_allreduce, grouped_allreduce_async,
    grouped_allreduce_, grouped_allreduce_async_,
    allgather, allgather_async, grouped_allgather, grouped_allgather_async,
    broadcast, broadcast_async, broadcast_, broadcast_async_,
    broadcast_object,
    allgather_object,
    alltoall, alltoall_async,
    reducescatter, reducescatter_async, grouped_reducescatter,
    synchronize, poll, wait, join, barrier,
    allreduce_p, allgather_p, broadcast_p, alltoall_p, reducescatter_p,
    stack_on_workers, worker_values,
)

from .compression import Compression  # noqa: F401
from .exceptions import (  # noqa: F401
    HorovodInternalError, HostsUpdatedInterrupt, NotInitializedError,
)

# --- optimizer wrappers (reference: horovod/torch/optimizer.py et al.) ------
from .optim import (  # noqa: F401
    DistributedOptimizer, DistributedGradientTransform,
    fused_reduce_scatter_tree, all_gather_sharded_tree,
    broadcast_parameters, broadcast_optimizer_state,
)
# overlapped dispatch context (ROADMAP item 3): wrap value_and_grad so
# the models' grad taps fire per-bucket collectives inside backprop
from .optim.overlap import overlapped_backprop  # noqa: F401

from . import elastic  # noqa: F401
# deterministic fault injection (docs/env.md "Chaos engineering"); pure
# stdlib, already loaded by the RPC layer's injection points
from . import chaos  # noqa: F401
# training-health telemetry (docs/observability.md "Training health"):
# hvd.health.note_loss / on_unhealthy are the user hooks
from . import health  # noqa: F401

# start-up's first span (docs/observability.md "Start-up and the jitted
# step"): this file top to bottom, jax's import included where the
# caller had not imported it yet
from . import tracing as _tracing
_tracing.span("setup", "import", _IMPORT_T0, _time.monotonic(), round=-1)


def __getattr__(name):
    if name == "global_process_set":
        from .runtime import _get_global_process_set
        return _get_global_process_set()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
