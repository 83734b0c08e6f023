"""What a per-head QK-norm and the rotation behind it cost, told by the scope
around them (``horovod_tpu/common/scopes.py``): ``hvd.attn.qknorm``, entered
by ``models/llama.py::LlamaAttention`` around the norm and the rotation of q
and k where the config has a QK-norm; forward, run again under recomputation
and backward, Mosaic calls (``hvd.rope`` nests inside it) and XLA operations
alike, so it reads the same work whatever implements it: the ``RMSNorm``
module and a pass of the rotation's, or the one pass that does both.  Inside
``hvd.block.attn``; ``block_attn_ms`` counts the XLA operations under it
too, ``flash_ms`` the Mosaic calls.  No roofline share of its own.

Read for ``benchmark/metrics/qk_norm_ms`` from the traced run's file with
``benchmark/scopes.py``'s reader; the name comes from the program's table,
and a program without it (the parent of the PR that added it), or a trace
with nothing under it, gives no number.
"""

from __future__ import annotations

import functools
import os

from benchmark import scopes, trace


@functools.lru_cache(maxsize=None)
def under_qk_norm(op_name: str, names) -> bool:
    """Whether the operation is under ``hvd.attn.qknorm``."""
    return any(scopes.bare(part) == names.QK_NORM
               for part in scopes.components(op_name))


def qk_norm_ms(events: dict, names) -> float | None:
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
            if under_qk_norm(op_name, names))
    if not steps or not total:
        return None
    return total * 1e3 / steps


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> float | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "QK_NORM"):
        return None
    ms = qk_norm_ms(scopes.read_events(path), names)
    if ms is not None:
        scopes.say(f"the QK-norm and the rotation of q and k, ms a step: "
                   f"{ms:.3f}")
    return ms


def scope_ms(ctx):
    """``qk_norm_ms`` of the traced run; None without a device trace, or
    without the scope in the program or the trace."""
    if ctx["trace"] is None:
        return None
    path = trace.find_xplane(scopes.TRACE_DIR)
    return _reduce_file(path, os.path.getmtime(path))
