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

The spans
---------
JAX's events stop at a program's edge: ``trace`` of ``hvd_train_step``,
12.8 s, full stop.  What ran INSIDE is the program's own Python, and that
the log times itself: ``span(name)`` is a context manager that keeps the
name, the start and the end on the records' clock, the thread, and the
span that was open on that thread when it began.  The program opens one
wherever it enters a scope of ``common/scopes.py`` (``scopes.scope``: the
``jax.named_scope`` and the span under ONE name, so a trace's seconds and
a step's device time are told by the same words), around every Mosaic
call's bind (``mosaic.<kernel>``, no ``named_scope``: what tracing the
kernels' bodies costs a trace), around every layer's call
(``layer.<mixer>.<ffn>``) and every differentiation rule of a ``custom_vjp``
(``rule.<op>.fwd`` / ``.bwd``; spans alone both, whose SELF time is what
JAX's interpreters did on that piece's behalf; ``hvd.loss`` also carries the
flag ``forward_seconds``, ``stamp``), inside ``hvd.init()`` (``hvd.init``,
``hvd.init.native``, ``hvd.init.distributed``, ``hvd.init.cache``) and,
from two stamps of the clock, around the package's import (``import
horovod_tpu.jax`` and its child ``import horovod_tpu.models``).
``compile_spans()`` gives them out, ``compile_spans(program)`` those that
lie inside ``program``'s ``trace`` records: the tree under "tracing
``hvd_train_step`` took 12.8 s".  Spans are recorded from the package's
import on, whether or not ``enable_compile_log()`` has run (the import and
``hvd.init`` come before it), kept in memory up to ``MAX_SPANS``, and
written out by nobody.  A span costs its two clock reads and an append
when the Python around it RUNS, which under ``jit`` is while JAX traces: a
step that runs from its compiled program runs none.  While it is open a
span also holds a ``jax.profiler.TraceAnnotation`` of its name (a flag
test while no profiler runs), so a profile taken across a recompile shows
the spans on the host thread's line, on the clock of the device's
operations.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import sys
import threading
import time
from typing import Optional

__all__ = ["default_cache_dir", "enable_compile_cache",
           "enable_compile_log", "compile_log", "compile_spans",
           "compile_evicted", "span", "stamp", "add_span", "CompileLog"]

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
    """What JAX reported about its compilations, in the order it came, and
    the spans the program opened itself.

    A record is ``{"program", "event", "seconds", "began"}``:

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
    The log keeps its newest ``MAX_RECORDS`` records: enough that a
    benchmark run's comparison (one record a function JAX traces: 2,600 for
    the ``xing4.0-29b-a4b`` cell's reference, PR 65) does not push out the
    train step's, which are the oldest.

    A span is ``{"name", "path", "began", "seconds", "self_seconds"}`` and
    whatever flags it was given: ``path`` its ancestors' names and its own
    joined by ``/`` (the spans that were open on its thread when it began,
    outermost first), ``self_seconds`` its duration less what its children
    cover.  ``began``, of a span and of a record, is in seconds from the
    log's origin (the first line of the package's import), so both order
    on one axis.  The log keeps the ``MAX_SPANS`` spans that ended last; a
    span that is still open is in no list.

    What either limit pushed out is COUNTED (``evicted()``): a sum over a
    log that lost its oldest part is no sum of the start, and a reader that
    finds a count above 0 says so and gives no number.
    """

    MAX_RECORDS = 16384
    MAX_SPANS = 16384
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

    def __init__(self, origin: Optional[float] = None) -> None:
        self._lock = threading.Lock()
        self._records: list = []       # of _Record, by time of arrival
        self._spans = collections.deque(maxlen=self.MAX_SPANS)  # by end
        self._evicted = {"records": 0, "spans": 0}
        self._open = threading.local()  # .stack: this thread's open spans
        self._origin = time.perf_counter() if origin is None else origin
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
            over = len(records) - self.MAX_RECORDS
            if over > 0:
                self._evicted["records"] += over
                del records[:over]

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
                     "seconds": r.seconds, "began": r.began - self._origin}
                    for r in self._records
                    if program is None or r.program in names]

    def evicted(self) -> dict:
        """``{"records", "spans"}``: how many of each the log has dropped
        to stay within ``MAX_RECORDS`` and ``MAX_SPANS``, the oldest
        first."""
        with self._lock:
            return dict(self._evicted)

    def _keep(self, span: "_Span") -> None:
        with self._lock:
            self._evicted["spans"] += len(self._spans) == self._spans.maxlen
            self._spans.append(span)

    def span(self, name: str, **flags) -> "_Span":
        """A context manager that times what runs inside it as the span
        ``name``, a child of the span open on this thread."""
        return _Span(self, name, flags)

    def stamp(self, flag: str) -> None:
        """Keep, as the flag ``flag`` of the innermost span open on this
        thread, the seconds it has run so far (``hvd.loss``'s
        ``forward_seconds``: where its forward half ended).  No span open:
        nothing is kept."""
        now = time.perf_counter()
        stack = getattr(self._open, "stack", None)
        if stack:
            stack[-1].flags[flag] = now - stack[-1].began

    def add_span(self, name: str, began: float, ended: float,
                 parent: Optional["_Span"] = None, **flags) -> "_Span":
        """Record a span that is over, from two readings of
        ``time.perf_counter`` (what ran before this module could be
        imported); ``parent`` is a span this call returned earlier."""
        span = _Span(self, name, flags)
        span.thread = threading.get_ident()
        span.began, span.ended = began, ended
        if parent is not None:
            span.path = parent.path + "/" + name
            parent.covered += ended - began
        self._keep(span)
        return span

    def spans(self, program: Optional[str] = None) -> list:
        """The spans that are over as dictionaries (copies), by their
        start; with ``program``, those that lie inside one of its
        ``trace`` records on that record's thread: what ran while JAX
        traced the function of that name."""
        with self._lock:
            spans = list(self._spans)
            traces = None if program is None else [
                (r.thread, r.began, r.at) for r in self._records
                if r.event == "trace" and r.program == program]
        if traces is not None:
            spans = [s for s in spans if any(
                s.thread == thread and began <= s.began and s.ended <= at
                for thread, began, at in traces)]
        spans.sort(key=lambda s: s.began)
        return [{**s.flags, "name": s.name, "path": s.path,
                 "began": s.began - self._origin,
                 "seconds": s.ended - s.began,
                 "self_seconds": max(s.ended - s.began - s.covered, 0.0)}
                for s in spans]


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


class _Span:
    """One span of a :class:`CompileLog`, and the context manager that
    times it: entered, it is the top of its thread's stack; left, it is in
    the log and its seconds are in its parent's ``covered``."""

    __slots__ = ("log", "name", "flags", "path", "thread", "began", "ended",
                 "covered", "parent", "annotation")

    def __init__(self, log: CompileLog, name: str, flags: dict) -> None:
        self.log, self.name, self.flags = log, name, flags
        self.path, self.covered, self.parent = name, 0.0, None

    def __enter__(self) -> "_Span":
        try:
            stack = self.log._open.stack
        except AttributeError:
            stack = self.log._open.stack = []
        if stack:
            self.parent = stack[-1]
            self.path = self.parent.path + "/" + self.name
        stack.append(self)
        self.thread = threading.get_ident()
        # On the profiler's clock too, where JAX is in the process and a
        # profile is being taken; no JAX is imported for it.
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self.annotation = profiler and profiler.TraceAnnotation(self.name)
        if self.annotation:
            self.annotation.__enter__()
        self.began = time.perf_counter()
        return self

    def __exit__(self, *error) -> None:
        self.ended = time.perf_counter()
        if self.annotation:
            self.annotation.__exit__(*error)
        stack = self.log._open.stack
        if stack[-1] is self:
            stack.pop()
        else:                   # left out of order: a suspended generator
            stack.remove(self)
        if self.parent is not None:
            self.parent.covered += self.ended - self.began
            self.parent = None
        self.log._keep(self)


# The origin of the process's log is the first line of the package's
# import (``horovod_tpu/__init__.py`` stamps the clock there, before it
# imports anything), so the import's own span begins at 0.
from horovod_tpu import IMPORT_BEGAN  # noqa: E402

_LOG = CompileLog(IMPORT_BEGAN)


def enable_compile_log() -> None:
    """Start the process's compile log (idempotent)."""
    _LOG.listen()


def compile_log(program: Optional[str] = None) -> list:
    """The process's compile log: see :class:`CompileLog`.  Empty until
    ``hvd.init()`` (or ``enable_compile_log()``) has run.
    ``hvd.compile_log(hvd.TRAIN_STEP_PROGRAM)`` picks the records of the
    step that ``make_train_step`` builds."""
    return _LOG.records(program)


def span(name: str, **flags):
    """``with span(name):`` times what runs inside it in the process's
    log: see :class:`CompileLog`."""
    return _LOG.span(name, **flags)


def stamp(flag: str) -> None:
    """The seconds the innermost open span of the process's log has run so
    far, kept as its flag ``flag``: see :meth:`CompileLog.stamp`."""
    _LOG.stamp(flag)


def add_span(name: str, began: float, ended: float, parent=None, **flags):
    """A finished span, from two ``time.perf_counter`` readings, into the
    process's log: see :meth:`CompileLog.add_span`."""
    return _LOG.add_span(name, began, ended, parent, **flags)


def compile_evicted() -> dict:
    """``{"records", "spans"}`` the process's log has dropped to stay within
    its limits (:meth:`CompileLog.evicted`): 0 and 0, or the oldest part of
    ``hvd.compile_log()`` / ``hvd.compile_spans()`` is gone and a sum over
    them is no sum of the start."""
    return _LOG.evicted()


def compile_spans(program: Optional[str] = None) -> list:
    """The process's spans: see :class:`CompileLog`.
    ``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)`` gives what ran while JAX
    traced the step that ``make_train_step`` builds, ``hvd.compile_spans()``
    those and the start-up's (``import horovod_tpu.jax``, ``hvd.init``)."""
    return _LOG.spans(program)
