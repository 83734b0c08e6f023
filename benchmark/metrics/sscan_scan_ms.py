"""Trace, by the program's scopes: self time a step of the operations under
``hvd.sscan.scan``: the selective scan itself, the Mosaic pair or the
``jnp`` body's chunks, and the sums over the backward call's partial
gradients; forward, recomputed and backward."""

from benchmark import sambay_scopes


def read(ctx):
    return sambay_scopes.scope_ms(ctx, "scan")
