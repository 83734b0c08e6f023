"""Process identity + lifecycle for the TPU-native Horovod rebuild.

Reference parity: ``horovod/common/__init__.py`` (HorovodBasics, the ctypes
bridge to the C ABI ``horovod_init/_shutdown/_rank/_size/_local_rank/
_local_size/_mpi_threads_supported`` declared in
``horovod/common/operations.h:68-98``).

TPU-native design
-----------------
Horovod's identity model is "one process per accelerator, ranks assigned by
mpirun".  On TPU the natural model is SPMD over a device mesh: one process per
*host*, each owning several chips, with JAX's distributed runtime (not MPI)
providing process_index/process_count.  We therefore keep Horovod's
rank/size/local_rank/local_size vocabulary but define it over *processes*
(hosts), and additionally expose device counts, because data parallelism on
TPU spans devices-within-a-process as well as processes.

The native C++ core (``horovod_tpu/cpp``, built separately) provides the
background coordinator (negotiation, fusion, timeline, stall detection)
behind the same C ABI as the reference.  This module loads it via ctypes when
the shared library is present, with a pure-Python fallback so the framework
is importable without the native build.
"""

from __future__ import annotations

import atexit
import ctypes
import os
import threading
from typing import Optional, Sequence

from horovod_tpu.common import scopes as _scopes

__all__ = ["HorovodBasics", "basics"]

# Env vars understood for rank discovery, in priority order.  The OMPI/PMI
# names are accepted for drop-in familiarity with the reference's mpirun
# workflow (reference test/common.py:24-56 reads the same names).
_RANK_ENV = ("HOROVOD_RANK", "OMPI_COMM_WORLD_RANK", "PMI_RANK")
_SIZE_ENV = ("HOROVOD_SIZE", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE")
_LOCAL_RANK_ENV = ("HOROVOD_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_RANK")
_LOCAL_SIZE_ENV = ("HOROVOD_LOCAL_SIZE", "OMPI_COMM_WORLD_LOCAL_SIZE")


def _env_int(names: Sequence[str]) -> Optional[int]:
    for name in names:
        value = os.environ.get(name)
        if value is not None and value != "":
            return int(value)
    return None


class HorovodBasics:
    """init/shutdown/rank/size lifecycle, optionally backed by the C++ core.

    Mirrors the reference ``HorovodBasics`` (common/__init__.py:51-154): the
    same method surface, raising if queried before ``init()``.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._initialized = False
        self._rank = 0
        self._size = 1
        self._local_rank = 0
        self._local_size = 1
        self._lib = None
        self._atexit_registered = False

    # -- lifecycle ---------------------------------------------------------

    def init(
        self,
        comm: Optional[Sequence[int]] = None,
        *,
        rank: Optional[int] = None,
        size: Optional[int] = None,
        local_rank: Optional[int] = None,
        local_size: Optional[int] = None,
        coordinator: Optional[str] = None,
        jax_distributed: Optional[bool] = None,
    ) -> None:
        """Initialize the runtime.

        ``comm`` accepts a rank subset (a list of WORLD ranks), matching the
        reference's ``hvd.init(comm=...)`` (common/__init__.py:58-84,
        operations.cc:1469-1488): the listed ranks form their own
        communicator — own rank numbering, own coordinator, own ring —
        and collectives span only them.  Processes NOT in the list
        initialize as a world of one (their collectives are identities),
        where the reference leaves them outside the MPI group entirely; a
        self-communicator is the functional equivalent without a second
        process group concept.  The subset coordinator listens on the world
        coordinator's port + 1 + min(comm) (deterministic and distinct for
        disjoint subsets); pass ``coordinator=`` to choose explicitly.
        mpi4py communicator objects are not accepted — there is no MPI here.

        Identity resolution order: explicit kwargs > HOROVOD_*/OMPI_*/PMI_*
        env vars > JAX distributed runtime (process_index/process_count) >
        single-process defaults.  Unlike the reference there is no MPI_Init:
        process rendezvous is the JAX coordination service's job (SURVEY.md
        §3.1 "TPU equivalent").

        ``jax_distributed=True`` (or ``HOROVOD_JAX_DISTRIBUTED=1``)
        additionally bootstraps JAX's own multi-process runtime
        (``jax.distributed.initialize``) from the same identity, so the
        launcher-provided rank/size/coordinator stands in for the pod's
        usual metadata discovery: after init, ``jax.devices()`` spans every
        process's chips and the jit/GSPMD path runs true multi-host.  The JAX
        coordination service listens on the engine coordinator's port + 64
        (override with ``HOROVOD_JAX_COORDINATOR=host:port``).  Must be
        called before the first JAX backend use, and is not compatible with
        ``comm=`` subsets (JAX has one global process group).
        """
        with self._lock:
            if self._initialized:
                return
            if comm is not None and not isinstance(comm, (list, tuple)):
                raise TypeError(
                    "comm must be a list of world ranks (mpi4py communicators "
                    "are not supported in the TPU-native runtime)"
                )

            if rank is None:
                rank = _env_int(_RANK_ENV)
            if size is None:
                size = _env_int(_SIZE_ENV)
            if (rank is None) != (size is None):
                raise ValueError(
                    "half-specified identity: rank and size must be given "
                    "together (via kwargs or HOROVOD_RANK/HOROVOD_SIZE "
                    "style env vars); got "
                    f"rank={rank!r}, size={size!r}"
                )
            from_jax = False
            if rank is None:
                rank, size = self._jax_identity()
                from_jax = True
            if local_rank is None:
                local_rank = _env_int(_LOCAL_RANK_ENV)
            if local_size is None:
                local_size = _env_int(_LOCAL_SIZE_ENV)
            if local_size is None:
                if from_jax:
                    # JAX multi-host deployments run one process per host.
                    local_size = 1
                else:
                    # Env-launched N processes with no local info: the
                    # single-host CI/test topology.
                    local_size = size
            if local_rank is None:
                local_rank = rank % local_size

            rank, size = int(rank), int(size)
            local_rank, local_size = int(local_rank), int(local_size)

            if comm:
                members = sorted({int(r) for r in comm})
                if members[0] < 0 or members[-1] >= size:
                    raise ValueError(
                        f"comm={members} contains ranks outside the world "
                        f"[0, {size})"
                    )
                world_rank, world_local_size = rank, local_size
                if world_rank not in members:
                    # Excluded process: world of one, no coordinator.
                    rank, size, local_rank, local_size = 0, 1, 0, 1
                else:
                    rank = members.index(world_rank)
                    size = len(members)
                    # Local identity follows the WORLD node layout so a
                    # subset spanning hosts still gets a meaningful
                    # intra-host split.
                    my_node = world_rank // world_local_size
                    same_node = [m for m in members
                                 if m // world_local_size == my_node]
                    local_rank = same_node.index(world_rank)
                    local_size = len(same_node)
                    if coordinator is None and size > 1:
                        base = os.environ.get("HOROVOD_COORDINATOR", "")
                        if base and ":" in base:
                            host, _, port = base.rpartition(":")
                            coordinator = (
                                f"{host}:{int(port) + 1 + members[0]}"
                            )

            if jax_distributed is None:
                jax_distributed = os.environ.get(
                    "HOROVOD_JAX_DISTRIBUTED", "") not in ("", "0")
            if jax_distributed and comm:
                raise ValueError(
                    "jax_distributed cannot be combined with comm= subsets "
                    "(JAX has one global process group)"
                )

            if not (0 < size and 0 <= rank < size):
                raise ValueError(
                    f"invalid identity: rank={rank}, size={size}"
                )
            if not (0 < local_size <= size and 0 <= local_rank < local_size):
                raise ValueError(
                    f"invalid local identity: local_rank={local_rank}, "
                    f"local_size={local_size} (size={size})"
                )

            # After identity validation, so a bad rank/size raises the
            # clear error above instead of hanging inside JAX's
            # coordination service.
            if jax_distributed and from_jax:
                raise ValueError(
                    "jax_distributed=True needs an explicit identity "
                    "(rank/size kwargs or HOROVOD_RANK/HOROVOD_SIZE env): "
                    "discovering it from JAX already initialized the "
                    "backend, which is too late for "
                    "jax.distributed.initialize"
                )
            if jax_distributed and size > 1:
                jaddr = os.environ.get("HOROVOD_JAX_COORDINATOR")
                if not jaddr:
                    base = coordinator or os.environ.get(
                        "HOROVOD_COORDINATOR", "")
                    if not base or ":" not in base:
                        raise ValueError(
                            "jax_distributed needs a coordinator address "
                            "(HOROVOD_COORDINATOR / coordinator= / "
                            "HOROVOD_JAX_COORDINATOR)"
                        )
                    host, _, port = base.rpartition(":")
                    jaddr = f"{host}:{int(port) + 64}"
                import jax

                # A retried init() after a failure elsewhere finds the JAX
                # runtime already up — that is fine.
                if not jax.distributed.is_initialized():
                    with _scopes.span(_scopes.INIT_DISTRIBUTED):
                        jax.distributed.initialize(
                            coordinator_address=jaddr,
                            num_processes=size,
                            process_id=rank,
                        )
            self._rank = rank
            self._size = size
            self._local_rank = local_rank
            self._local_size = local_size

            with _scopes.span(_scopes.INIT_NATIVE):
                self._start_native(coordinator)
            self._initialized = True
            self._maybe_start_autotuner()
            self._maybe_start_monitor()
            if not self._atexit_registered:
                # Reference registers shutdown via atexit (common/__init__.py:69).
                atexit.register(self.shutdown)
                self._atexit_registered = True

    def _start_native(self, coordinator: Optional[str]) -> None:
        """Find (or build) and load the C++ core, and start its engine
        with this process's identity: the span ``hvd.init.native``."""
        self._load_native()
        if self._lib is not None:
            if os.environ.get("HOROVOD_AUTOTUNE", "0") not in ("", "0"):
                # Warm start for the WIRING-time knobs: the state
                # file's probed channels/drivers must land in the env
                # before horovod_init wires the rings (explicit user
                # env values win inside the helper).
                from horovod_tpu.autotune.store import (
                    apply_wiring_warm_start,
                )

                apply_wiring_warm_start(os.environ)
            addr = coordinator or os.environ.get("HOROVOD_COORDINATOR", "")
            ret = self._lib.horovod_init(
                self._rank,
                self._size,
                self._local_rank,
                self._local_size,
                addr.encode(),
            )
            if ret != 0:
                try:
                    detail = self._lib.horovod_last_error().decode()
                except Exception:
                    detail = ""
                raise RuntimeError(
                    f"native horovod_init failed with code {ret}"
                    + (f": {detail}" if detail else "")
                )
            # Adopt the COMMITTED identity: under elastic membership
            # (HOROVOD_ELASTIC=1) the coordinator may have re-formed
            # the world around the survivors — contiguous re-ranked,
            # smaller (or re-grown) size — so the env-pinned identity
            # is only the join candidacy, not the final word.  Gated
            # on the elastic flag: outside it the engine never
            # reassigns, and the process-wide engine singleton may
            # predate this (test-local) HorovodBasics instance.
            if os.environ.get("HOROVOD_ELASTIC", "") not in ("", "0"):
                self._rank = int(self._lib.horovod_rank())
                self._size = int(self._lib.horovod_size())

    def _maybe_start_autotuner(self) -> None:
        """Start the online autotuner thread on the coordinator when
        HOROVOD_AUTOTUNE=1 (default 0: no thread, no TUNE frames — the
        untuned path is behaviorally untouched).  The probe's re-init
        churn sets HOROVOD_AUTOTUNE_SUSPEND so mid-probe worlds are
        never tuned underneath the measurement."""
        if self._lib is None or self._size <= 1 or self._rank != 0:
            return
        if os.environ.get("HOROVOD_AUTOTUNE", "0") in ("", "0"):
            return
        if os.environ.get("HOROVOD_AUTOTUNE_SUSPEND", "") not in ("", "0"):
            return
        from horovod_tpu.autotune.tuner import start_autotuner
        from horovod_tpu.runtime.engine import get_engine

        start_autotuner(get_engine())

    def _maybe_start_monitor(self) -> None:
        """Start the live metrics endpoint on rank 0 when
        HOROVOD_METRICS_PORT is set (default unset: no thread, no
        socket — provably off).  Serves Prometheus text on /metrics and
        JSON on /json from the engine's stats() + fleet table; see
        docs/observability.md."""
        port_raw = os.environ.get("HOROVOD_METRICS_PORT", "")
        if self._lib is None or self._rank != 0 or port_raw in ("", "0"):
            return
        try:
            port = int(port_raw)
        except ValueError:
            import sys

            print(f"horovod_tpu: bad HOROVOD_METRICS_PORT={port_raw!r}; "
                  "metrics endpoint disabled", file=sys.stderr)
            return
        from horovod_tpu.monitor.server import start_metrics_server
        from horovod_tpu.runtime.engine import get_engine

        import sys

        eng = get_engine()
        try:
            bound = start_metrics_server(port, eng.stats, eng.fleet_stats)
        except (OSError, RuntimeError) as exc:
            # Monitoring must degrade, never fail init: a busy port
            # (stale job, two jobs on one box) costs the endpoint, not
            # the training run.
            print(f"horovod_tpu: metrics endpoint disabled: {exc}",
                  file=sys.stderr)
            return
        print(f"horovod_tpu: metrics endpoint on :{bound} "
              "(/metrics /json /fleet)", file=sys.stderr)

    def fleet_stats(self) -> dict:
        """Rank 0's fleet telemetry table (``{}`` on workers, with
        telemetry off, or before the first TELEM frame) — see
        :meth:`horovod_tpu.runtime.engine.NativeEngine.fleet_stats`."""
        if self._lib is None:
            return {}
        from horovod_tpu.runtime.engine import get_engine

        return get_engine().fleet_stats()

    def shutdown(self) -> None:
        # Stop the monitor first: it only reads counters, but its
        # providers must not race the native teardown's state swaps.
        if os.environ.get("HOROVOD_METRICS_PORT", "") not in ("", "0"):
            from horovod_tpu.monitor.server import stop_metrics_server

            stop_metrics_server()
        # Stop the tuner BEFORE taking the lock and the engine down: its
        # thread only reads counters/queues frames, but it must not race
        # the native shutdown with a TUNE proposal.
        if os.environ.get("HOROVOD_AUTOTUNE", "0") not in ("", "0"):
            from horovod_tpu.autotune.tuner import stop_autotuner

            stop_autotuner()
        with self._lock:
            if not self._initialized:
                return
            if self._lib is not None:
                self._lib.horovod_shutdown()
                # A later init() restarts the native core with an empty
                # tensor table; the Python wrapper's auto-name counters
                # must restart with it or unnamed collectives never
                # rendezvous with relaunched peers (elastic recovery).
                from horovod_tpu.runtime.engine import reset_engine_naming

                reset_engine_naming()
            self._initialized = False

    # -- queries -----------------------------------------------------------

    def _check(self) -> None:
        if not self._initialized:
            # Same contract as reference CheckInitialized (operations.cc:1933).
            raise ValueError(
                "Horovod has not been initialized; use hvd.init()."
            )

    def is_initialized(self) -> bool:
        return self._initialized

    def rank(self) -> int:
        self._check()
        return self._rank

    def size(self) -> int:
        self._check()
        return self._size

    def local_rank(self) -> int:
        self._check()
        return self._local_rank

    def local_size(self) -> int:
        self._check()
        return self._local_size

    def epoch(self) -> int:
        """Committed membership epoch — 0 before init or without the
        native core.  Bumped by every successful rendezvous commit, so an
        in-place elastic resize (shrink to survivors, worker rejoin)
        increments it on every live member; control frames from older
        epochs are structurally rejected by the engine."""
        if self._lib is None or not hasattr(self._lib, "horovod_epoch"):
            return 0
        return int(self._lib.horovod_epoch())

    def mpi_threads_supported(self) -> bool:
        """Parity shim: there is no MPI; the coordination service is
        inherently multi-threaded, so report True (reference
        common/__init__.py:147-154)."""
        self._check()
        return True

    # -- helpers -----------------------------------------------------------

    @staticmethod
    def _jax_identity() -> tuple[int, int]:
        try:
            import jax

            return jax.process_index(), jax.process_count()
        except Exception:
            return 0, 1

    def _load_native(self) -> None:
        if self._lib is not None:
            return
        from horovod_tpu.common.native_build import ensure_native_lib

        path = ensure_native_lib()
        if path is None:
            return
        try:
            lib = ctypes.CDLL(path, mode=ctypes.RTLD_GLOBAL)
        except OSError:
            return
        lib.horovod_init.argtypes = [
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_char_p,
        ]
        lib.horovod_init.restype = ctypes.c_int
        lib.horovod_shutdown.argtypes = []
        lib.horovod_shutdown.restype = None
        if hasattr(lib, "horovod_last_error"):
            lib.horovod_last_error.argtypes = []
            lib.horovod_last_error.restype = ctypes.c_char_p
        if hasattr(lib, "horovod_epoch"):
            lib.horovod_epoch.argtypes = []
            lib.horovod_epoch.restype = ctypes.c_int64
        self._lib = lib

    @property
    def native_lib(self):
        """The loaded C++ core (ctypes CDLL) or None."""
        return self._lib


#: Singleton, mirroring the reference's module-level basics object.
basics = HorovodBasics()
