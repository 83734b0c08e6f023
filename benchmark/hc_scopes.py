"""A hyper-connected decoder's residual streams' share of a step, told by the
scopes they add (``horovod_tpu/common/scopes.py``): ``hvd.hc.map`` (a
sublayer's three maps: the RMS over a token's streams, the one product,
gains, biases, sigmoids and Sinkhorn's steps) and ``hvd.hc.mix`` (the read
``h_pre X`` and the write ``H_res X + h_post^T y``); forward, recomputed and
backward alike, whatever runs them.  The mixes have a share of a roofline
from what the job says the ALGORITHM has to move
(``benchmark/arithmetic_hc.py``), which reads the same whatever implements
the pass.

Read for ``benchmark/metrics/hc_map_ms``, ``hc_mix_ms`` and
``hc_mix_roofline`` from the traced run's file with ``benchmark/scopes.py``'s
reader, the way ``benchmark/lconv_scopes.py`` reads the short convolutions';
the names come from the program's table, and a program without them (the
parent of the PR that added them) gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, trace

KINDS = ("map", "mix")


@functools.lru_cache(maxsize=None)
def classify(op_name: str, names):
    """Which of ``KINDS`` the operation is under, or None.  The two do not
    nest; the innermost decides if they ever do."""
    for part in reversed(scopes.components(op_name)):
        for kind in KINDS:
            if scopes.bare(part) == getattr(names, "HC_" + kind.upper()):
                return kind
    return None


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step by kind, averaged over the chips that ran
    operations, and ``<kind>_recomputed``: the part that ran again under
    JAX's ``rematted_computation``.  None where no operation is of either
    kind."""
    total = dict.fromkeys(
        KINDS + tuple(kind + "_recomputed" for kind in KINDS), 0.0)
    steps = 0
    for _, device in sorted(events["devices"].items()):
        if not (device["ops"] and device["modules"]):
            continue
        start, end, executions = trace.step_window(device["modules"])
        steps += executions
        for (_, op_name), own in trace.self_times(
                trace.clip(device["ops"], start, end)):
            kind = classify(op_name, names)
            if kind is None:
                continue
            total[kind] += own
            if names.REMATTED in op_name:
                total[kind + "_recomputed"] += own
    if not steps or not any(total.values()):
        return None
    return {kind: seconds * 1e3 / steps for kind, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "HC_MIX"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("hyper-connected streams, ms a step: " + ", ".join(
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


def mix_roofline(ctx):
    """The least time the chip could take for a step's mixes over the time
    the trace shows under ``hvd.hc.mix``, in per cent."""
    ms = scope_ms(ctx, "mix")
    work = ctx["job"]["kernel_work_per_step"].get("hc_mix")
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"hc_mix roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
