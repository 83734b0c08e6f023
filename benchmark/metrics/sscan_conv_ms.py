"""Trace, by the program's scopes: self time a step of the operations under
``hvd.sscan.conv``: the Mamba-1 layers' causal depthwise convolution over
their u channels, the filter's bias and the SiLU; forward, recomputed and
backward."""

from benchmark import sambay_scopes


def read(ctx):
    return sambay_scopes.scope_ms(ctx, "conv")
