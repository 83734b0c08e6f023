"""Trace, by the program's scopes: self time a step of the operations under
``hvd.kda.gates``: the Kimi Delta Attention layers' low-rank projection to a
log-decay a key channel, its softplus, beta, all in float32; the per-head norm
of the rule's output and the sigmoid gate on it."""

from benchmark import kda_scopes


def read(ctx):
    return kda_scopes.scope_ms(ctx, "gates")
