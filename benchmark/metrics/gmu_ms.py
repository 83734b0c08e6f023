"""Trace, by the program's scopes: self time a step of the operations under
``hvd.gmu``: the gated memory units whole (``x W_1``, the gate on the shared
memory ``m silu(x W_1)``, ``W_2``) and their gradients; forward, recomputed
and backward.  The whole unit: XLA fuses the gate product into ``W_2``'s
matmul, so the product alone has no operation of its own to time."""

from benchmark import sambay_scopes


def read(ctx):
    return sambay_scopes.scope_ms(ctx, "gmu")
