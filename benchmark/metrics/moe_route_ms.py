"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.moe.route`` and ``hvd.moe.combine``: the
routed layers' router, top-k, balance loss, sort by expert, gather of the
held experts' rows and the rows' way back under their gates, forward,
recomputed and backward."""

from benchmark import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "route")
