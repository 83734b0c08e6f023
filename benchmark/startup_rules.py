"""What JAX does for the program while it traces a step, told by the piece of
the program it did it for; and what the log's records say of the two
programs of a start that had no reader.

``benchmark/startup_spans.py`` reads the spans the program opens wherever it
enters a scope.  They leave half of a step's trace under no name: the seconds
JAX's own interpreters run between them (``trace_loss_self_ms``).  Every one
of those seconds is spent on behalf of a piece that the program CAN bracket,
and since PR 67 it does, with spans ALONE (``horovod_tpu/common/scopes.py``:
no ``named_scope``, nothing of them in the HLO):

* ``layer.<mixer>.<ffn>`` around every call of a layer (``layer.resnet.
  stage<n>`` around a stage).  The block scopes nest inside; the span's SELF
  time is what JAX did for that layer outside the program's Python:
  ``jax.checkpoint`` tracing and staging it, the JVP and the partial
  evaluation of its jaxpr;
* ``rule.<op>.fwd`` / ``rule.<op>.bwd`` around every differentiation rule of
  a ``custom_vjp``.  Self time: what JAX ran because the rule asked
  (``jax.vjp`` of a buffer's body inside ``_live_buffers_bwd``, for one).
  Backward rules run at the top of ``hvd.loss``;
* ``hvd.loss`` carries the flag ``forward_seconds``: how long after its start
  the forward half (``jax.vjp``) had been traced.  The rest is the pullback.

The reductions take lists of spans and records, so the tests drive them with
hand-made ones; a reader gives None, never a wrong number, where the program
keeps none of this (the parent of PR 67) and where the log says it has
dropped its oldest part (``hvd.compile_evicted()``).  Everything is summed
over the process so far, as in ``startup_spans``.
"""

from __future__ import annotations

import functools
from collections import defaultdict

from benchmark import startup_spans
from benchmark.scopes import say
from benchmark.startup_spans import named, prefixed, total_ms

#: The name JAX reports the benchmark's state program under
#: (``benchmark/run.py::set_up`` jits a function of this name).
STATE_PROGRAM = "make_state"


# -- reductions of a list of spans -------------------------------------------

def by_name(spans) -> list:
    """``[name, entries, total ms, self ms]`` of every distinct name, in the
    order first entered: one row a layer kind, one a rule and pass."""
    rows = defaultdict(lambda: [0, 0.0, 0.0])
    for s in spans:
        row = rows[s["name"]]
        row[0] += 1
        row[1] += 1e3 * s["seconds"]
        row[2] += 1e3 * s["self_seconds"]
    return [[name, *row] for name, row in rows.items()]


def halves(spans, loss: str, flag: str):
    """``{"forward", "backward", "backward_self"}`` in ms of the spans
    called ``loss`` that carry ``flag``, or None where none does.  The
    backward half begins ``flag`` seconds into the span; its self time is
    the half less every span that began in it (the children of ``loss``
    that did: the stamp is taken with none open, so what began behind it
    and nests deeper lies inside one of them)."""
    found = [s for s in named(spans, loss) if flag in s]
    if not found:
        return None
    forward = backward = covered = 0.0
    for top in found:
        turned = top["began"] + top[flag]
        ended = top["began"] + top["seconds"]
        forward += top[flag]
        backward += top["seconds"] - top[flag]
        covered += sum(
            s["seconds"] for s in spans
            if s["path"] == top["path"] + "/" + s["name"]
            and turned <= s["began"] and s["began"] + s["seconds"] <= ended)
    return {"forward": 1e3 * forward, "backward": 1e3 * backward,
            "backward_self": 1e3 * max(backward - covered, 0.0)}


# -- reductions of a list of records -----------------------------------------

def event_ms(records, event: str):
    """Milliseconds of the records of ``event``; None where there is none."""
    found = [r["seconds"] for r in records if r["event"] == event]
    return 1e3 * sum(found) if found else None


def retrieval_and_load(records):
    """``{"retrieval", "load"}`` in ms: what JAX reports as the persistent
    cache's retrieval of the executable, and the rest of the backend's
    seconds; None where a request missed the cache (the backend COMPILED
    then) or none was made.  (On the v5e the retrieval is all but 14-19 ms
    of the backend's 1.2-8 s: JAX's event brackets the file's read AND the
    executable's deserialisation and load, PERF.md, PR 67.)"""
    def count(event):
        return sum(r["event"] == event for r in records)

    retrieval, backend = (event_ms(records, e) for e in (
        "cache_retrieval", "backend"))
    if (retrieval is None or backend is None
            or count("cache_request") != count("cache_hit")):
        return None
    return {"retrieval": retrieval, "load": backend - retrieval}


# -- the program's log -------------------------------------------------------

def program():
    """``(hvd, the table of names)`` where the program keeps the layer and
    rule spans and counts what its log dropped, else None."""
    found = startup_spans.program()
    if found is None:
        return None
    hvd, names = found
    if not hasattr(hvd, "compile_evicted") or not hasattr(names, "RULE"):
        return None
    return found


@functools.lru_cache(maxsize=1)
def _say_rules() -> bool:
    """Once a run: whether the log is whole, and if it is the line of the
    layer kinds, the rules and the two halves."""
    hvd, names = program()
    evicted = hvd.compile_evicted()
    if any(evicted.values()):
        say(f"start-up rules: the compile log dropped {evicted['spans']} "
            f"span(s) and {evicted['records']} record(s), its oldest; a "
            f"sum over it is no sum of the start, so no number is given")
        return False
    spans = hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)
    layers, rules = (prefixed(spans, p) for p in (names.LAYER, names.RULE))
    said = [f"{name} x{n} {total:.3f} (self {own:.3f})"
            for name, n, total, own in by_name(layers) + by_name(rules)]
    loss = named(spans, names.LOSS)
    split = halves(spans, names.LOSS, names.FORWARD_SECONDS)
    if split is not None:
        under = [s for s in spans
                 if s["path"].split("/")[0] == names.LOSS]
        said.append(
            f"{names.LOSS} {total_ms(loss):.3f} = forward "
            f"{split['forward']:.3f} + backward {split['backward']:.3f} "
            f"(backward self {split['backward_self']:.3f}); self seconds "
            f"of the {len(under)} spans under it add up to "
            f"{total_ms(under, 'self_seconds'):.3f}")
    say("start-up rules, ms: " + "; ".join(said)
        + f"; {len(layers)} {names.LAYER}* and {len(rules)} {names.RULE}* "
        f"spans in the step's trace; evicted: {evicted['spans']} spans, "
        f"{evicted['records']} records")
    return True


def log():
    """``(hvd, the table)`` where the program has the new spans and its log
    is whole, else None."""
    found = program()
    if found is None or not _say_rules():
        return None
    return found


def step_spans():
    found = log()
    if found is None:
        return None
    hvd, names = found
    return hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM), names


def loss_half_ms(half: str):
    """``halves``' ``half`` (``"forward"``, ``"backward"``,
    ``"backward_self"``) of the step's ``hvd.loss``."""
    found = step_spans()
    if found is None:
        return None
    spans, names = found
    split = halves(spans, names.LOSS, names.FORWARD_SECONDS)
    return None if split is None else split[half]


def prefix_self_ms(prefix: str):
    """Self milliseconds of the step's spans under the prefix ``prefix`` of
    the program's table (``LAYER``, ``RULE``)."""
    found = step_spans()
    if found is None:
        return None
    spans, names = found
    return total_ms(prefixed(spans, getattr(names, prefix)), "self_seconds")


def step_cache_ms(part: str):
    """``retrieval_and_load``'s ``part`` (``"retrieval"``, ``"load"``) of
    the train step's records."""
    found = log()
    if found is None:
        return None
    hvd, _ = found
    split = retrieval_and_load(hvd.compile_log(hvd.TRAIN_STEP_PROGRAM))
    return None if split is None else split[part]


def state_ms(event: str):
    """Milliseconds JAX reported for ``event`` of the state's program."""
    found = log()
    if found is None:
        return None
    return event_ms(found[0].compile_log(STATE_PROGRAM), event)
