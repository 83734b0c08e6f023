"""horovod_tpu: a TPU-native distributed training framework.

A ground-up rebuild of Horovod 0.15.1's capabilities (reference:
``/root/reference``) designed for TPUs: SPMD over ``jax.sharding.Mesh``
device meshes, XLA collectives on ICI/DCN instead of NCCL/MPI, trace-time
tensor fusion instead of staging buffers, and a native C++ coordinator for
the host-driven (eager / PyTorch) path.

Frontends (mirroring ``horovod.tensorflow`` / ``horovod.torch`` /
``horovod.keras``):

* ``horovod_tpu.jax`` — flagship, for JAX/flax/optax training.

(``horovod_tpu.torch`` and ``horovod_tpu.keras`` frontends are planned; see
SURVEY.md §7 steps 5-6.)
"""

import time as _time

#: The clock (``time.perf_counter``) at the first line of the package's
#: import: where the compile log's span ``import horovod_tpu.jax`` begins,
#: and the origin of every ``began`` the log gives out
#: (``common/compile_cache.py``).
IMPORT_BEGAN = _time.perf_counter()

from horovod_tpu import elastic  # noqa: E402
from horovod_tpu.common import (  # noqa: E402
    epoch,
    fleet_stats,
    init,
    is_initialized,
    local_rank,
    local_size,
    mpi_threads_supported,
    rank,
    shutdown,
    size,
)
from horovod_tpu.version import __version__  # noqa: E402

__all__ = [
    "__version__",
    "elastic",
    "init",
    "shutdown",
    "is_initialized",
    "rank",
    "size",
    "local_rank",
    "local_size",
    "epoch",
    "fleet_stats",
    "mpi_threads_supported",
]
