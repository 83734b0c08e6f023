"""Trace, by the program's scopes: self time a step of the operations under
``hvd.ssd.proj``: the Mamba-2 layers' two projections (``[hidden, 2 H P + 2
G N + H]`` to z, x, B, C and dt, and the output's) and their gradient
products; forward, recomputed and backward.  A part of ``block_attn_ms``."""

from benchmark import ssd_dense_scopes


def read(ctx):
    return ssd_dense_scopes.proj_ms(ctx)
