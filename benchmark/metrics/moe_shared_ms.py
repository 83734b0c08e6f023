"""Trace, by the program's scopes: self time a step of the operations under
``hvd.moe.shared``: the shared experts' SwiGLU, which every chip of the
deployment computes for its own tokens."""

from benchmark import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "shared")
