"""Trace, by the program's scopes: self time a step of the operations under
``hvd.kda.conv``: the Kimi Delta Attention layers' three causal depthwise
filters, their SiLU and the L2 norms of q and k; forward, recomputed and
backward."""

from benchmark import kda_scopes


def read(ctx):
    return kda_scopes.scope_ms(ctx, "conv")
