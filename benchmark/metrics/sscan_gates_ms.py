"""Trace, by the program's scopes: self time a step of the operations under
``hvd.sscan.gates``: the Mamba-1 layers' projections from u to the step's
rank, B and C, the rank's projection, its bias and the softplus, and behind
the scan the gate ``y silu(z)``; forward, recomputed and backward."""

from benchmark import sambay_scopes


def read(ctx):
    return sambay_scopes.scope_ms(ctx, "gates")
