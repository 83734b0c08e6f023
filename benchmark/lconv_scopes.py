"""A double-gated short-convolution layer's share of a step, told by the
scopes it adds (``horovod_tpu/common/scopes.py``): ``hvd.lconv.proj`` (the
``[hidden, 3 hidden]`` projection to the gates B, C and the filter's input z,
the output projection, and their gradient products) and ``hvd.lconv.conv``
(gate, filter, gate: ``C (taps * (B z))``); forward, recomputed and backward
alike, Mosaic calls and XLA operations alike.  The filter has a share of a
roofline from what the job says the ALGORITHM needs
(``benchmark/arithmetic_lconv.py``), which reads the same whatever implements
the pass.

Read for ``benchmark/metrics/lconv_proj_ms``, ``lconv_conv_ms`` and
``lconv_conv_roofline`` from the traced run's file with
``benchmark/scopes.py``'s reader, the way ``benchmark/ssd_scopes.py`` reads
the Mamba-2 layers'; the names come from the program's table, and a program
without them (the parent of the PR that added them) gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, trace

KINDS = ("proj", "conv")


@functools.lru_cache(maxsize=None)
def classify(op_name: str, names):
    """Which of ``KINDS`` the operation is under, or None.  The two do not
    nest; the innermost decides if they ever do."""
    for part in reversed(scopes.components(op_name)):
        for kind in KINDS:
            if scopes.bare(part) == getattr(names, "LCONV_" + kind.upper()):
                return kind
    return None


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step by kind, averaged over the chips that ran
    operations, and ``conv_recomputed``: the part of ``conv`` that ran again
    under JAX's ``rematted_computation``.  None where no operation is of
    either kind."""
    total = dict.fromkeys(KINDS + ("conv_recomputed",), 0.0)
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
            if kind == "conv" and names.REMATTED in op_name:
                total["conv_recomputed"] += own
    if not steps or not any(total.values()):
        return None
    return {kind: seconds * 1e3 / steps for kind, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "LCONV_CONV"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("gated short-convolution layers, ms a step: " + ", ".join(
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


def conv_roofline(ctx):
    """The least time the chip could take for a step's gated filters over
    the time the trace shows under ``hvd.lconv.conv``, in per cent."""
    ms = scope_ms(ctx, "conv")
    work = ctx["job"]["kernel_work_per_step"].get("lconv_conv")
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"lconv_conv roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
