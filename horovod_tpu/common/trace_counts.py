"""Which body a trace took, and why not the other: one table for every op.

An op that chooses between a Mosaic call and a ``jnp`` body, a layout and
another, a fused update and one on its own, chooses while JAX traces (under
``jit`` nothing of the choice is left at run time), and a production config
that loses a kernel should not do so silently.  So each such choice is noted
here, once a TRACE, under a ``kind`` (the op's own name for the choice) and a
``reason`` (which way it went, or why not the other), and the op's public
view (``ops/flash_attention.py::fallback_count`` and ``layout_counts``,
``ops/short_conv.py::body_counts``, ``ops/gated_delta.py::solve_counts``,
``hvd.update_counts``) reads its kind back.  Process-global, under one lock:
tracing can run on several threads.  Plain Python, no JAX.
"""

from __future__ import annotations

import threading
import warnings

__all__ = ["note", "note_last", "counts"]

_lock = threading.Lock()
_counts: dict = {}      # (kind, reason) -> traces


def note(kind: str, reason, *, warn: str = "") -> None:
    """Count one trace of ``kind`` that went ``reason``'s way.  With
    ``warn`` the first trace of each reason also raises a ``RuntimeWarning``
    of that text and the reason, at the caller's caller."""
    with _lock:
        first = (kind, reason) not in _counts
        _counts[kind, reason] = _counts.get((kind, reason), 0) + 1
    if warn and first:
        warnings.warn(warn + str(reason), RuntimeWarning, stacklevel=3)


def note_last(kind: str, tally: dict) -> None:
    """``kind``'s counts become ``tally`` (reason -> n): for a choice made
    many times in one trace, whose view describes the last trace alone."""
    with _lock:
        for key in [key for key in _counts if key[0] == kind]:
            del _counts[key]
        _counts.update({(kind, reason): n for reason, n in tally.items()})


def counts(kind: str) -> dict:
    """``{reason: n}`` of ``kind``, the reasons in the order first noted."""
    with _lock:
        return {reason: n for (k, reason), n in _counts.items() if k == kind}
