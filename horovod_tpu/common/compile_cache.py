"""A persistent XLA compile cache whose place can be chosen from outside.

Compiling the decoder train step for a TPU takes tens of seconds, and a
cold process pays it again unless JAX's persistent compilation cache is
on.  The cache directory is part of nothing the program computes, but it
must not move between runs (a directory that moves never hits), so:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
  sets no other directory;
* otherwise, on an accelerator — ``<checkout>/.jax_cache``, resolved from
  this package's own location: the same path from any working directory,
  never from ``tempfile``, a pid or a clock.  ``.gitignore`` lists it;
* otherwise, on the CPU backend — nothing.  XLA:CPU executables are built
  for the compiling host's CPU features, and a cache that sits in the tree
  travels with it to other machines (the chip tool copies the disk).

Wherever the cache is on, every program goes into it, however small and
however quickly it compiled: JAX's defaults leave out what compiles in
under a second, and a job's dozen small programs (initialisation, data,
metrics) then compile again in every run, a second in all on a v5e
(PERF.md, PR 23's set-up study).

Called by the entry points that run jitted programs
(``horovod_tpu.jax.init``, ``serve/replica.py``).  It asks JAX for its
backend, so it runs after any ``jax.distributed.initialize``.

The compile log
---------------
``enable_compile_log()`` (``hvd.init()`` calls it, on every backend)
listens to what JAX itself reports through ``jax.monitoring`` whenever it
traces, lowers or compiles a program, and ``compile_log()`` gives the
records out.  It answers "why did my job take 90 s to start" (which
program, and was it tracing, lowering, compiling or reading the cache) and
"which step recompiled" (a second ``trace`` record of one program).  JAX
calls a listener only when it compiles, so a step that runs from its
compiled program costs the log nothing.  Nothing is printed.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional

__all__ = ["default_cache_dir", "enable_compile_cache",
           "enable_compile_log", "compile_log", "CompileLog"]

_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the ``horovod_tpu`` package."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on where it pays; return the
    directory in force, or None when the cache is left off."""
    import jax

    path = os.environ.get(_ENV)
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = default_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """What JAX reported about its compilations, in the order it came.

    A record is ``{"program", "event", "seconds"}``:

    ===================  ==================================================
    ``event``            ``seconds``
    ===================  ==================================================
    ``trace``            tracing the Python function ``program``
                         (``hvd_train_step``) to a jaxpr.  JAX reports
                         every jitted function it traces on the way
                         (``jnp.where``, a flax module's ``jit``): their
                         time is part of the outer function's, and only the
                         outermost is kept.  A call that finds its trace in
                         JAX's cache still reports, with microseconds
    ``lower``            jaxpr to MLIR module; ``program`` is the module's
                         name (``jit(hvd_train_step)``), as for ``backend``
    ``backend``          XLA's backend compiling the module, or fetching it
                         from the persistent cache
    ``cache_request``    the persistent cache was asked (None: a count)
    ``cache_hit``        and had the program (None: a count)
    ``cache_retrieval``  reading it from there, inside ``backend``'s seconds
    ===================  ==================================================

    JAX names no program in the three cache events; they come inside a
    backend compilation, whose record follows them, so they take that
    record's program (two threads that compile at once can swap theirs).
    The log keeps its newest ``MAX_RECORDS`` records.
    """

    MAX_RECORDS = 4096
    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    }
    COUNTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_request",
        "/jax/compilation_cache/cache_hits": "cache_hit",
    }

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._records: list = []       # of _Record, by time of arrival
        self._listening = False

    def listen(self) -> None:
        """Register with ``jax.monitoring``, once."""
        import jax

        with self._lock:
            if self._listening:
                return
            self._listening = True
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _add(self, event: str, seconds, program) -> None:
        new = _Record(program, event, seconds, time.perf_counter(),
                      threading.get_ident())
        with self._lock:
            records = self._records
            if event == "trace":
                # JAX reports a function when its tracing ends: what this
                # thread traced since this one began was traced inside it.
                i = len(records)
                while i and records[i - 1].at >= new.began:
                    i -= 1
                records[i:] = [r for r in records[i:] if not (
                    r.event == "trace" and r.thread == new.thread
                    and r.began >= new.began)]
            elif event == "backend":
                for r in reversed(records):
                    if r.program is not None:
                        break
                    r.program = program
            records.append(new)
            del records[:-self.MAX_RECORDS]

    def _on_event(self, event: str, **_) -> None:
        if event in self.COUNTS:
            self._add(self.COUNTS[event], None, None)

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event in self.DURATIONS:
            self._add(self.DURATIONS[event], seconds,
                      kwargs.get("fun_name"))

    def records(self, program: Optional[str] = None) -> list:
        """The records as dictionaries; with ``program``, those of the
        function of that name (its ``trace`` records, and what JAX reports
        under ``jit(<program>)``: lowering, backend, cache)."""
        names = (program, f"jit({program})")
        with self._lock:
            return [{"program": r.program, "event": r.event,
                     "seconds": r.seconds} for r in self._records
                    if program is None or r.program in names]


@dataclasses.dataclass
class _Record:
    program: Optional[str]
    event: str
    seconds: Optional[float]
    at: float                   # perf_counter when JAX reported it
    thread: int

    @property
    def began(self) -> float:
        return self.at - (self.seconds or 0.0)


_LOG = CompileLog()


def enable_compile_log() -> None:
    """Start the process's compile log (idempotent)."""
    _LOG.listen()


def compile_log(program: Optional[str] = None) -> list:
    """The process's compile log: see :class:`CompileLog`.  Empty until
    ``hvd.init()`` (or ``enable_compile_log()``) has run.
    ``hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)`` picks the records of the
    step that ``make_train_step`` builds."""
    return _LOG.records(program)
