"""Trace, by the program's scopes: self time a step of the operations under
``hvd.lconv.proj``: the double-gated short-convolution layers' two
projections (``[hidden, 3 hidden]`` to the gates B, C and the filter's input
z, and the output's) and their gradient products; forward, recomputed and
backward.  A part of ``block_attn_ms``."""

from benchmark import lconv_scopes


def read(ctx):
    return lconv_scopes.scope_ms(ctx, "proj")
