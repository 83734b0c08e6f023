"""Trace, by the program's scopes: self time a step of the operations under
``hvd.lconv.conv``: the double-gated short-convolution layers' gate, filter
and gate, ``C (taps * (B z))``; forward, recomputed and backward; Mosaic
calls and XLA operations alike."""

from benchmark import lconv_scopes


def read(ctx):
    return lconv_scopes.scope_ms(ctx, "conv")
