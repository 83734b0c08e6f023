"""A routed-expert decoder's share of a step, told by the scopes it adds:
latent attention's way from x to the kernel's keys and values
(``hvd.mla.latent``), and of the routed layer the routing
(``hvd.moe.route`` and ``hvd.moe.combine``: router, top-k, balance loss,
sort, gather, and the rows back under their gates), the grouped products
over the held experts (``hvd.moe.experts``) and the shared experts
(``hvd.moe.shared``); forward, recomputed and backward alike.  Read for
``benchmark/metrics/moe_route_ms``, ``moe_experts_ms``,
``moe_experts_roofline``, ``moe_shared_ms`` and ``mla_latent_ms`` from the
traced run's file with ``benchmark/scopes.py``'s reader; the names come
from the program's table, and a program without them (the parent of the PR
that added them) gives no number.

XLA:TPU runs ``jax.lax.ragged_dot`` as Mosaic calls that it names itself
(``ragged-dot-none``, and ``ragged-dot-metadata`` for the tiles), with no
scope of the program's in their ``op_name``: the table's
``RAGGED_DOT_PREFIX`` tells them, and they count as the experts'.
"""

from __future__ import annotations

import functools
import os

from benchmark import arithmetic, scopes, trace

KINDS = ("route", "experts", "shared", "latent")


@functools.lru_cache(maxsize=None)
def classify(op_name: str, names) -> str | None:
    """Which of ``KINDS`` the operation whose ``op_name`` path is
    ``op_name`` belongs to, or None."""
    if op_name.startswith(names.RAGGED_DOT_PREFIX):
        return "experts"
    held = {scopes.bare(part) for part in scopes.components(op_name)}
    if names.MOE_EXPERTS in held:
        return "experts"
    if names.MOE_ROUTE in held or names.MOE_COMBINE in held:
        return "route"
    if names.MOE_SHARED in held:
        return "shared"
    if names.MLA_LATENT in held:
        return "latent"
    return None


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
        for (_, op_name), own in trace.self_times(
                trace.clip(device["ops"], start, end)):
            kind = classify(op_name, names)
            if kind:
                total[kind] += own
    if not steps or not any(total.values()):
        return None
    return {kind: seconds * 1e3 / steps for kind, seconds in total.items()}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "MOE_ROUTE"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("latent attention and the routed layers, ms a step: "
                   + ", ".join(f"{kind} {ms:.3f}"
                               for kind, ms in reduced.items()))
    return reduced


def scope_ms(ctx, kind: str):
    """``kind`` of ``partition`` for the traced run; None without a device
    trace, or without these scopes in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    reduced = _reduce_file(path, os.path.getmtime(path))
    return None if reduced is None else reduced[kind] or None


def experts_roofline(ctx):
    """The least time the chip could take for a step's grouped products at
    the rows the held experts expect (what the job says they need:
    ``benchmark/arithmetic_moe.py``) over ``moe_experts_ms``, in per
    cent."""
    ms = scope_ms(ctx, "experts")
    if not ms or ctx["peaks"] is None:
        return None
    work = ctx["job"]["kernel_work_per_step"].get("moe_experts")
    if work is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    scopes.say(f"routed experts' roofline: {bound} bound, least "
               f"{least_s * 1e3:.3f} ms a step")
    return 100.0 * least_s * 1e3 / ms
