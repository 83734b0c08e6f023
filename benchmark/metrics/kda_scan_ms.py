"""Trace, by the program's scopes: self time a step of everything under
``hvd.kda.scan``, the chunkwise delta rule with a decay a channel
(``ops/kda.py``): cutting into chunks, every chunk's systems A and P, the
triangular solve, the walk that carries the state; forward,
run again under recomputation and backward; Mosaic calls and XLA operations
alike."""

from benchmark import kda_scopes


def read(ctx):
    return kda_scopes.scope_ms(ctx, "scan")
