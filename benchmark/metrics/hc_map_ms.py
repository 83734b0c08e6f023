"""Trace, by the program's scopes: self time a step of the operations under
``hvd.hc.map``: the three maps of every hyper-connected sublayer (the RMS over
a token's streams, the ``[streams hidden, streams (streams + 2)]`` product,
gains and biases, the two sigmoids and Sinkhorn's steps on the residual
map); forward, recomputed and backward."""

from benchmark import hc_scopes


def read(ctx):
    return hc_scopes.scope_ms(ctx, "map")
