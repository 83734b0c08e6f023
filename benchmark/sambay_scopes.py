"""A decoder-hybrid-decoder stack's share of a step, told by the scopes it
adds (``horovod_tpu/common/scopes.py``): ``hvd.sscan.conv`` (a Mamba-1
layer's causal depthwise convolution, its bias and SiLU), ``hvd.sscan.gates``
(the projections to the step, B and C, the softplus; behind the scan the
gate), ``hvd.sscan.scan`` (the selective scan: the Mosaic pair or the ``jnp``
body's chunks), ``hvd.gmu`` (a gated memory unit whole: its two products
and the gate on the shared memory between them) and ``hvd.attn.diff`` (differential attention behind its two calls:
lambda, the subtraction, the pair's norm); forward, recomputed and backward
alike, Mosaic calls and XLA operations alike.  The scan has a share of a
roofline from what the job says the ALGORITHM needs
(``benchmark/arithmetic_sambay.py``), which reads the same whatever
implements it and low by construction (the work is the vector unit's).

Read for ``benchmark/metrics/sscan_conv_ms``, ``sscan_gates_ms``,
``sscan_scan_ms``, ``sscan_scan_roofline``, ``gmu_ms`` and ``diff_attn_ms``
from the traced run's file with ``benchmark/scopes.py``'s reader, the way
``benchmark/ssd_scopes.py`` reads Mamba-2's; the names come from the
program's table, and a program without them (the parent of the PR that added
them) gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, trace

KINDS = {"conv": "SSCAN_CONV", "gates": "SSCAN_GATES", "scan": "SSCAN_SCAN",
         "gmu": "GMU", "diff": "ATTN_DIFF"}


@functools.lru_cache(maxsize=None)
def classify(op_name: str, names):
    """Which of ``KINDS`` the operation is under, or None.  The five do not
    nest; the innermost decides if they ever do."""
    for part in reversed(scopes.components(op_name)):
        for kind, constant in KINDS.items():
            if scopes.bare(part) == getattr(names, constant):
                return kind
    return None


def partition(events: dict, names) -> dict | None:
    """Milliseconds a step by kind, averaged over the chips that ran
    operations, and ``scan_mosaic``: the part of ``scan`` that is Mosaic
    calls.  None where no operation is of any kind."""
    total = dict.fromkeys((*KINDS, "scan_mosaic"), 0.0)
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
            if kind == "scan" and trace.op_kind(text) == "mosaic":
                total["scan_mosaic"] += own
    if not steps or not any(total.values()):
        return None
    return {kind: seconds * 1e3 / steps for kind, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "SSCAN_SCAN"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("Mamba-1 layers, memory units and differential "
                   "attention, ms a step: " + ", ".join(
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


def scan_roofline(ctx):
    """The least time the chip could take for a step's selective scans over
    the time the trace shows under ``hvd.sscan.scan``, in per cent."""
    ms = scope_ms(ctx, "scan")
    work = ctx["job"]["kernel_work_per_step"].get("sscan")
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"sscan roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
