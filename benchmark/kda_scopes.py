"""A Kimi Delta Attention layer's share of a step, told by the scopes it adds
(``horovod_tpu/common/scopes.py``): ``hvd.kda.conv`` (the three causal
filters, their SiLU, the L2 norms), ``hvd.kda.gates`` (the low-rank
projection to a log-decay a key channel, beta; the output's per-head norm
under its sigmoid gate) and ``hvd.kda.scan`` (the chunkwise rule with a decay
a channel: every chunk's systems and solve and the walk that carries the
state); forward, recomputed and backward alike, Mosaic calls and XLA
operations alike.  The scan has a share of a roofline from what the job says
the ALGORITHM needs (``benchmark/arithmetic_kda.py``), which reads the same
whatever implements it.

Read for ``benchmark/metrics/kda_conv_ms``, ``kda_gates_ms``, ``kda_scan_ms``
and ``kda_scan_roofline`` from the traced run's file with
``benchmark/scopes.py``'s reader; the names come from the program's table,
and a program without them (the parent of the PR that added them) gives no
number and raises nothing.
"""

from __future__ import annotations

import collections
import functools
import os

from benchmark import arithmetic_kda, gdn_scopes, scopes, trace

KINDS = gdn_scopes.KINDS            # conv, gates, scan: the same three

#: The program's table as ``gdn_scopes`` reads it, with this layer's scopes
#: under the names it looks up (hashable: its classification is cached).
_AsGdn = collections.namedtuple(
    "_AsGdn", ["GDN_CONV", "GDN_GATES", "GDN_SCAN", "REMATTED"])


def _as_gdn(names):
    return _AsGdn(names.KDA_CONV, names.KDA_GATES, names.KDA_SCAN,
                  names.REMATTED)


def classify(op_name: str, names):
    """Which of ``KINDS`` the operation is under, or None: the innermost of
    the three decides (``hvd.gdn.solve``, the shared solve, nests inside
    ``hvd.kda.scan`` and is counted there)."""
    return gdn_scopes.classify(op_name, _as_gdn(names))


def partition(events: dict, names) -> dict | None:
    """``gdn_scopes.partition`` by this layer's three scopes: milliseconds a
    step by kind, and ``scan_recomputed``; None where no operation is of any
    kind."""
    return gdn_scopes.partition(events, _as_gdn(names))


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _stamp: float) -> dict | None:
    names = scopes.program_scopes()
    if names is None or not hasattr(names, "KDA_SCAN"):
        return None
    reduced = partition(scopes.read_events(path), names)
    if reduced is not None:
        scopes.say("Kimi Delta Attention layers, ms a step: " + ", ".join(
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
    """The least time the chip could take for a step's chunked rule over the
    time the trace shows under ``hvd.kda.scan``, in per cent."""
    ms = scope_ms(ctx, "scan")
    work = ctx["job"]["kernel_work_per_step"].get("kda_scan")
    if not ms or work is None or ctx["peaks"] is None:
        return None
    least_ms, bound = arithmetic_kda.roofline_ms(work, ctx["peaks"])
    scopes.say(f"kda_scan roofline: {bound} bound, least {least_ms:.3f} ms "
               f"a step")
    return 100.0 * least_ms / ms
