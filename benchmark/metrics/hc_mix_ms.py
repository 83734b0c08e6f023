"""Trace, by the program's scopes: self time a step of the operations under
``hvd.hc.mix``: every hyper-connected sublayer's read ``h_pre X`` and write
``H_res X + h_post^T y`` of the residual streams, and their gradients;
forward, recomputed and backward, whatever runs them."""

from benchmark import hc_scopes


def read(ctx):
    return hc_scopes.scope_ms(ctx, "mix")
