"""The least time the chip could take for the sliding layers' attention over
their bands alone in a step (``benchmark/arithmetic_window.py``: seven
products a kept pair over ``seq * window - window (window - 1) / 2`` pairs a
head, forward 2 and backward 5, nothing recomputed; q, k, v, o, dO, dq, dk
and dv once) over ``window_attn_ms``.  The count is the algorithm's, from
shapes: it reads the same whatever blocks the calls walk, and pairs
executed outside the band lower it."""

from benchmark import window_scopes


def read(ctx):
    return window_scopes.window_roofline(ctx)
