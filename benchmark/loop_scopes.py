"""A looped model's share of a step, told by the scopes it adds: the passes
over the weight-shared stack (``hvd.loop.pass``: the scan whole, its
stacking of residuals and its sum of gradients over passes included), what
ends each pass (the final norm and the exit gate, inside that scan, and
each exit's head and loss: ``hvd.loop.exit``), and the forward work the
backward pass repeats, which JAX names itself (``rematted_computation``,
inside ``transpose(...)``).  Read for
``benchmark/metrics/loop_stack_ms``, ``loop_exit_ms`` and ``recompute_ms``
from the traced run's file with ``benchmark/scopes.py``'s reader; the
names come from the program's table, and a program without them (the
parent of the PR that added them) gives no number.

An operation under both scopes is the exit's.  What is under neither is the
embedding's lookup and its scatter-add, and what XLA hoists out of the
passes (the rotary tables).
"""

from __future__ import annotations

import functools
import os

from benchmark import scopes, trace

@functools.lru_cache(maxsize=None)
def classify(op_name: str, names) -> tuple:
    """(``"stack"``, ``"exit"`` or None; whether it is recomputed forward
    work) for the operation whose ``op_name`` path is ``op_name``."""
    held = {scopes.bare(part) for part in scopes.components(op_name)}
    if names.LOSS not in held:
        return None, False
    if names.LOOP_EXIT in held:
        kind = "exit"
    elif names.LOOP_PASS in held:
        kind = "stack"
    else:
        kind = None
    return kind, names.REMATTED in held


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step, averaged over the chips that ran operations:
    ``{"stack": ms, "exit": ms, "recompute": ms, "recompute_exit": ms}``
    (the last is the part of ``recompute`` under ``hvd.loop.exit``).  None
    where no operation is under either scope."""
    total = dict.fromkeys(("stack", "exit", "recompute", "recompute_exit"),
                          0.0)
    steps = 0
    for _, device in sorted(events["devices"].items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        for (_, op_name), own in trace.self_times(
                trace.clip(device["ops"], start, end)):
            kind, recomputed = classify(op_name, names)
            if kind:
                total[kind] += own
            if recomputed:
                total["recompute"] += own
                if kind == "exit":
                    total["recompute_exit"] += own
    if not steps or not (total["stack"] or total["exit"]):
        return None
    return {key: seconds * 1e3 / steps for key, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "LOOP_PASS"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("a looped step, ms: " + ", ".join(
            f"{key} {ms:.3f}" for key, ms in reduced.items()))
    return reduced


def loop_ms(ctx, key: str):
    """``key`` of ``partition`` for the traced run; None without a device
    trace, or without the loop's scopes in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    reduced = _reduce_file(path, os.path.getmtime(path))
    return None if reduced is None else reduced[key]
