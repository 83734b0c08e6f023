"""Trace, by the program's scopes: self time a step of the operations under
``hvd.gdn.conv``: the gated delta-rule layers' three causal depthwise
convolutions, their SiLU and the L2 norms of q and k; forward, recomputed
and backward."""

from benchmark import gdn_scopes


def read(ctx):
    return gdn_scopes.scope_ms(ctx, "conv")
