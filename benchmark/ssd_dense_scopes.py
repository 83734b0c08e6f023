"""What a Mamba-2 layer with a feed-forward of its own adds to
``benchmark/ssd_scopes.py``'s reading of a step: the time under
``hvd.ssd.proj`` (``horovod_tpu/common/scopes.py``: ``in_proj`` to z, x, B, C
and dt, ``out_proj``, and their gradient products; forward, recomputed and
backward alike), and the gates' share of a roofline, ``ssd_gates_ms`` (that
module's) against what the job says the ALGORITHM needs
(``benchmark/arithmetic_ssm_dense.py``: the bytes of y, v, z and the result
each way, nothing recomputed), which reads the same whatever implements the
pass.

Read for ``benchmark/metrics/ssd_proj_ms`` and ``ssd_gates_roofline`` from
the traced run's file with ``benchmark/scopes.py``'s reader; the name comes
from the program's table, and a program without it (the parent of the PR
that added it) gives no number, as a job that states no ``ssd_gates`` work
gives no share.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, ssd_scopes, trace


@functools.lru_cache(maxsize=None)
def is_projection(op_name: str, names) -> bool:
    """Whether the operation is under ``hvd.ssd.proj``."""
    return any(scopes.bare(part) == names.SSD_PROJ
               for part in scopes.components(op_name))


def projections_ms(events: dict, names) -> float | None:
    """Milliseconds a step under ``hvd.ssd.proj``, averaged over the chips
    that ran operations.  None where no operation is under it."""
    total, steps = 0.0, 0
    for _, device in sorted(events["devices"].items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        total += sum(own for (_, op_name), own in trace.self_times(
            trace.clip(device["ops"], start, end))
            if is_projection(op_name, names))
    if not steps or not total:
        return None
    return total * 1e3 / steps


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> float | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "SSD_PROJ"):
        return None
    ms = projections_ms(scopes.read_events(path), names)
    if ms is not None:
        scopes.say(f"Mamba-2 layers' projections, ms a step: {ms:.3f}")
    return ms


def proj_ms(ctx):
    """``projections_ms`` for the traced run; None without a device trace,
    or without the scope in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    return _reduce_file(path, os.path.getmtime(path))


def gates_roofline(ctx):
    """The least time the chip could take for a step's skips, gates and
    norms over the time the trace shows under ``hvd.ssd.gates``, in per
    cent."""
    ms = ssd_scopes.scope_ms(ctx, "gates")
    work = ctx["job"]["kernel_work_per_step"].get("ssd_gates")
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"ssd_gates roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
