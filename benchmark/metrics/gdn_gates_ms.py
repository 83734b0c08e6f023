"""Trace, by the program's scopes: self time a step of the operations under
``hvd.gdn.gates``: the gated delta-rule layers' decay and beta gates in
float32, the per-head norm of the rule's output and the SiLU gate on it."""

from benchmark import gdn_scopes


def read(ctx):
    return gdn_scopes.scope_ms(ctx, "gates")
