"""Where a process's start goes, told by the program's own spans.

The program times its own Python on the host wherever it enters a scope of
``horovod_tpu/common/scopes.py`` (at TRACE time: a step that runs from its
executable runs none of it), around every Mosaic call's bind
(``mosaic.<kernel>``), inside ``hvd.init()`` and, from two stamps of the
clock, around the package's import; ``hvd.compile_spans()`` gives the spans
out, ``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)`` those that lie inside the
train step's ``trace`` records (``horovod_tpu/common/compile_cache.py``).  A
span is ``{"name", "path", "began", "seconds", "self_seconds"}``: ``path``
its ancestors' names and its own joined by ``/``, ``self_seconds`` its
duration less what its children cover.

This module reduces them for the readers of ``benchmark/metrics`` that move
``setup_s`` from inside (``import_hvd_ms``, ``init_ms``, ``init_native_ms``
and the ``trace_*`` family), beside ``benchmark/scopes.py``'s
``step_compile_ms``, which times the same layer from outside, and says the
whole tree once a run.  The reductions take a list of spans, so the tests
drive them with hand-made ones.  A program without ``compile_spans`` (the
parent of the PR that added it) gives no number, not a wrong one.

Everything is summed over the process so far, as ``step_compile_ms`` is: the
step is traced in the set-up and nowhere else.
"""

from __future__ import annotations

import functools
from collections import defaultdict

from benchmark.scopes import say


# -- reductions of a list of spans -------------------------------------------

def named(spans, *names) -> list:
    """The spans called one of ``names``."""
    return [s for s in spans if s["name"] in names]


def outermost(spans, *names) -> list:
    """The spans called one of ``names`` that lie in no other such span:
    ``hvd.head`` inside ``hvd.loop.exit`` inside ``hvd.head`` counts once."""
    return [s for s in named(spans, *names)
            if not set(s["path"].split("/")[:-1]) & set(names)]


def prefixed(spans, prefix: str) -> list:
    """Every span whose name starts with ``prefix``, wherever it nests."""
    return [s for s in spans if s["name"].startswith(prefix)]


def total_ms(spans, key: str = "seconds"):
    """Milliseconds of ``spans`` added up; None where there is none."""
    if not spans:
        return None
    return 1e3 * sum(s[key] for s in spans)


def tree(spans) -> list:
    """``[path, entries, total ms, self ms]`` of every distinct path, in
    the order first entered."""
    rows = defaultdict(lambda: [0, 0.0, 0.0])
    for s in spans:
        row = rows[s["path"]]
        row[0] += 1
        row[1] += 1e3 * s["seconds"]
        row[2] += 1e3 * s["self_seconds"]
    return [[path, *row] for path, row in rows.items()]


# -- the program's log -------------------------------------------------------

def program():
    """``(hvd, the table of names)`` where the program keeps spans, else
    None."""
    import horovod_tpu.jax as hvd
    from horovod_tpu.common import scopes

    if not hasattr(hvd, "compile_spans") or not hasattr(scopes, "MOSAIC"):
        return None
    return hvd, scopes


@functools.lru_cache(maxsize=1)
def _say_tree() -> None:
    """Once a run: the start-up's spans and every path of the step's
    trace, so a run's log holds the tree and the metrics its headlines."""
    hvd, names = program()
    start = [s for s in hvd.compile_spans() if s["path"].split("/")[0] in (
        names.IMPORT, names.INIT)]
    step = hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)
    said = [f"{path} {total:.3f}" for path, _, total, _ in tree(start)]
    said += [f"{path} x{n} {total:.3f} (self {own:.3f})"
             for path, n, total, own in tree(step)]
    say("start-up spans, ms: " + "; ".join(said)
        + f"; {len(step)} spans in the step's trace, "
        f"{len(hvd.compile_spans())} in the log")


def start_ms(name: str):
    """Milliseconds of the start-up's spans called ``name`` (a constant of
    the program's table: ``IMPORT``, ``INIT``, ``INIT_NATIVE``)."""
    found = program()
    if found is None:
        return None
    hvd, names = found
    _say_tree()
    return total_ms(named(hvd.compile_spans(), getattr(names, name)))


def step_spans():
    """``(the spans inside the train step's trace, the table)`` or None."""
    found = program()
    if found is None:
        return None
    hvd, names = found
    _say_tree()
    return hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM), names


def trace_ms(*scopes: str):
    """Milliseconds of the step's trace under the outermost spans of the
    scopes ``scopes`` (constants of the program's table)."""
    found = step_spans()
    if found is None:
        return None
    spans, names = found
    return total_ms(outermost(spans, *(getattr(names, s) for s in scopes)))


def trace_self_ms(scope: str):
    """Milliseconds of the step's trace in the spans of ``scope`` and in
    no span inside them."""
    found = step_spans()
    if found is None:
        return None
    spans, names = found
    return total_ms(named(spans, getattr(names, scope)), "self_seconds")


def kernel_binds():
    """The ``mosaic.*`` spans of the step's trace, or None."""
    found = step_spans()
    if found is None:
        return None
    spans, names = found
    return prefixed(spans, names.MOSAIC)
