"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.loop.exit``: the final norm and the exit gate
after every pass, each exit's head and cross-entropy (forward, recomputed
and backward) and the exit distribution."""

from benchmark import loop_scopes


def read(ctx):
    return loop_scopes.loop_ms(ctx, "exit")
