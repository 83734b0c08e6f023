"""What a gated delta-rule layer with fewer key heads than value heads pays
to make them as many, told by the scope it adds
(``horovod_tpu/common/scopes.py``): ``hvd.gdn.heads``, q and k copied to the
value heads (value head j reads key head ``j // (value / key)``) and, in the
backward pass, the sum over each key head's copies; forward, run again under
recomputation and backward.  A rule that indexed the key head inside its walk
would have nothing here: the number is there to be removed.

Read for ``benchmark/metrics/gdn_heads_ms`` from the traced run's file with
``benchmark/scopes.py``'s reader; the name comes from the program's table,
and a program without it (the parent of the PR that added it), or a trace
with nothing under it (a layer with as many key heads as value heads never
enters the scope), gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import scopes, trace


@functools.lru_cache(maxsize=None)
def under_heads(op_name: str, names) -> bool:
    """Whether the operation is under ``hvd.gdn.heads``."""
    return any(scopes.bare(part) == names.GDN_HEADS
               for part in scopes.components(op_name))


def heads_ms(events: dict, names) -> float | None:
    """Milliseconds a step under the scope, averaged over the chips that
    ran operations.  None where no operation is under it."""
    total, steps = 0.0, 0
    for _, device in sorted(events["devices"].items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        total += sum(own for (_, op_name), own in trace.self_times(
            trace.clip(device["ops"], start, end))
            if under_heads(op_name, names))
    if not steps or not total:
        return None
    return total * 1e3 / steps


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> float | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "GDN_HEADS"):
        return None
    ms = heads_ms(scopes.read_events(path), names)
    if ms is not None:
        scopes.say(f"key heads copied to value heads, ms a step: {ms:.3f}")
    return ms


def scope_ms(ctx):
    """``heads_ms`` of the traced run; None without a device trace, or
    without the scope in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    return _reduce_file(path, os.path.getmtime(path))
