"""A sliding-window layer's attention and the per-head output gate in a
step, told by the scopes they add (``horovod_tpu/common/scopes.py``):
``hvd.attn.window`` (a windowed layer's rotation of q and k and its two
flash calls over the band; forward, run again under recomputation and
backward, Mosaic calls and XLA operations alike) and ``hvd.attn.gate`` (the
gate's projection, sigmoid, its way to a head's lanes and the multiply, and
their gradients, in full and sliding layers alike).  The band has a share
of a roofline from what the job says the ALGORITHM needs
(``benchmark/arithmetic_window.py``: the band's own pairs, whatever blocks
the calls walk), so executing masked pairs lowers it and nothing raises it
over 100 %.

Read for ``benchmark/metrics/window_attn_ms``, ``window_attn_roofline`` and
``attn_gate_ms`` from the traced run's file with ``benchmark/scopes.py``'s
reader; the names come from the program's table, and a program without
them (the parent of the PR that added them) gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, trace

KINDS = {"window": "ATTN_WINDOW", "gate": "ATTN_GATE"}


@functools.lru_cache(maxsize=None)
def classify(op_name: str, names):
    """Which of ``KINDS`` the operation is under, or None.  The two do not
    nest; the innermost decides if they ever do."""
    for part in reversed(scopes.components(op_name)):
        for kind, constant in KINDS.items():
            if scopes.bare(part) == getattr(names, constant):
                return kind
    return None


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step by kind, averaged over the chips that ran
    operations, and ``window_mosaic``: the part of ``window`` that is
    Mosaic calls (the flash calls and the rotation).  None where no
    operation is of either kind."""
    total = dict.fromkeys((*KINDS, "window_mosaic"), 0.0)
    steps = 0
    for _, device in sorted(events["devices"].items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        for (text, op_name), own in trace.self_times(
                trace.clip(device["ops"], start, end)):
            kind = classify(op_name, names)
            if kind is None:
                continue
            total[kind] += own
            if kind == "window" and trace.op_kind(text) == "mosaic":
                total["window_mosaic"] += own
    if not steps or not any(total.values()):
        return None
    return {kind: seconds * 1e3 / steps for kind, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "ATTN_WINDOW"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("window layers and gates, ms a step: " + ", ".join(
            f"{kind} {ms:.3f}" for kind, ms in reduced.items()))
    return reduced


def scope_ms(ctx, kind: str):
    """``kind`` of ``partition`` for the traced run; None without a device
    trace, or without these scopes in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    reduced = _reduce_file(path, os.path.getmtime(path))
    return None if reduced is None else reduced[kind] or None


def window_roofline(ctx):
    """The least time the chip could take for a step's attention over the
    sliding layers' bands over the time the trace shows under
    ``hvd.attn.window``, in per cent."""
    ms = scope_ms(ctx, "window")
    work = ctx["job"]["kernel_work_per_step"].get("window_attn")
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"window_attn roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
