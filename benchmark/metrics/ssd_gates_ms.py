"""Trace, by the program's scopes: self time a step of the operations under
``hvd.ssd.gates``: the Mamba-2 layers' ``softplus(dt + dt_bias)`` in float32
and, behind the scan, the skip ``D u``, the gate ``silu(z)`` and the grouped
RMSNorm behind it."""

from benchmark import ssd_scopes


def read(ctx):
    return ssd_scopes.scope_ms(ctx, "gates")
