"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.loop.pass`` and not ``hvd.loop.exit``: a
looped model's passes over its weight-shared stack, forward, recomputed and
backward, the flash calls and the sum of gradients over passes included."""

from benchmark import loop_scopes


def read(ctx):
    return loop_scopes.loop_ms(ctx, "stack")
