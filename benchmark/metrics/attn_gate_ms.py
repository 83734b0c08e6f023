"""Trace, by the program's scopes: self time a step of the operations under
``hvd.attn.gate``: the per-head output gate's projection, its sigmoid, the
gate's way to its head's lanes and the multiply on the attention's output,
and their gradients; forward, recomputed and backward, every layer."""

from benchmark import window_scopes


def read(ctx):
    return window_scopes.scope_ms(ctx, "gate")
