"""Learned sparse attention's share of a step, told by the scopes it adds
(``horovod_tpu/common/scopes.py``): ``hvd.sparse.index`` (the indexer's
projections and rotations, and the Mosaic call that forms its loss and its
gradients) and ``hvd.sparse.select`` (the Mosaic call that scores a query
block against its causal keys and selects); forward, recomputed and
backward alike.  The two Mosaic calls are told by the scope they run under
and read by themselves as well (``index_loss`` and ``index_select``), with
their share of a roofline from what the job says they need
(``benchmark/arithmetic_sparse.py``).  The attention over the selected keys
runs under the flash kernel's scopes and is read with it.

Read for ``benchmark/metrics/sparse_index_ms``, ``sparse_select_ms``,
``index_loss_ms``, ``index_loss_roofline``, ``index_select_ms`` and
``index_select_roofline`` from the traced run's file with
``benchmark/scopes.py``'s reader; the names come from the program's table,
and a program without them (the parent of the PR that added them) gives no
number.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, trace

KINDS = ("index", "select", "index_loss", "index_select")
#: a Mosaic call's kind (also its key in the job's ``kernel_work_per_step``)
#: and the scope it runs under
CALLS = {"index_loss": "index", "index_select": "select"}


@functools.lru_cache(maxsize=None)
def classify(name: str, op_name: str, names) -> tuple:
    """Which of ``KINDS`` the operation belongs to: its scope's, and the
    Mosaic call's own beside it."""
    held = {scopes.bare(part) for part in scopes.components(op_name)}
    for call, scope in CALLS.items():
        if getattr(names, "SPARSE_" + scope.upper()) in held:
            if trace.op_kind(name) == "mosaic":
                return scope, call
            return (scope,)
    return ()


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step by kind, averaged over the chips that ran
    operations; None where no operation is of any kind."""
    total = dict.fromkeys(KINDS, 0.0)
    steps = 0
    for _, device in sorted(events["devices"].items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        for (name, op_name), own in trace.self_times(
                trace.clip(device["ops"], start, end)):
            for kind in classify(name, op_name, names):
                total[kind] += own
    if not steps or not any(total.values()):
        return None
    return {kind: seconds * 1e3 / steps for kind, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "SPARSE_INDEX"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("learned sparse attention, ms a step: " + ", ".join(
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


def call_roofline(ctx, kind: str):
    """The least time the chip could take for a step's calls of ``kind``
    over the time the trace shows them take, in per cent."""
    ms = scope_ms(ctx, kind)
    work = ctx["job"]["kernel_work_per_step"].get(kind)
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"{kind} roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
