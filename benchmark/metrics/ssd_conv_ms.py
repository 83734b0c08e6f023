"""Trace, by the program's scopes: self time a step of the operations under
``hvd.ssd.conv``: the Mamba-2 layers' causal depthwise convolution over
their x, B and C channels, the filter's bias and the SiLU; forward,
recomputed and backward."""

from benchmark import ssd_scopes


def read(ctx):
    return ssd_scopes.scope_ms(ctx, "conv")
