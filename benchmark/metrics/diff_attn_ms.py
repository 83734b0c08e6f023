"""Trace, by the program's scopes: self time a step of the operations under
``hvd.attn.diff``: differential attention behind its two flash calls a layer
(lambda from its four vectors, ``a1 - lambda a2``, the RMSNorm over a head
pair's value lanes, the factor ``1 - lambda_init``) and their gradients."""

from benchmark import sambay_scopes


def read(ctx):
    return sambay_scopes.scope_ms(ctx, "diff")
