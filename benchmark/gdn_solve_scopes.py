"""What a gated delta-rule layer's chunk systems cost, told by the scope
around them (``horovod_tpu/common/scopes.py``): ``hvd.gdn.solve``, the
inverse of each chunk's unit lower-triangular ``I + A`` and its transpose
(``ops/gated_delta.py::_tril_inverse``); forward, run again under
recomputation and backward, the Mosaic call and XLA operations alike.  The
scope lies INSIDE ``hvd.gdn.scan``: what is counted here is counted in
``gdn_scan_ms`` too (``benchmark/gdn_scopes.py::classify`` walks outward to
the kind it knows).  No roofline share of its own: ``gdn_scan_roofline``
counts the algorithm and reads the same whatever solves the systems.

Read for ``benchmark/metrics/gdn_solve_ms`` from the traced run's file with
``benchmark/scopes.py``'s reader; the name comes from the program's table,
and a program without it (the parent of the PR that added it), or a trace
with nothing under it, gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import scopes, trace


@functools.lru_cache(maxsize=None)
def under_solve(op_name: str, names) -> bool:
    """Whether the operation is under ``hvd.gdn.solve``."""
    return any(scopes.bare(part) == names.GDN_SOLVE
               for part in scopes.components(op_name))


def solve_ms(events: dict, names) -> float | None:
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
            if under_solve(op_name, names))
    if not steps or not total:
        return None
    return total * 1e3 / steps


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> float | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "GDN_SOLVE"):
        return None
    ms = solve_ms(scopes.read_events(path), names)
    if ms is not None:
        scopes.say(f"the chunk systems' solves, ms a step: {ms:.3f}")
    return ms


def scope_ms(ctx):
    """``solve_ms`` of the traced run; None without a device trace, or
    without the scope in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    return _reduce_file(path, os.path.getmtime(path))
