"""Trace, by the program's scopes: self time a step of everything under
``hvd.gdn.scan``, the chunkwise gated delta rule
(``ops/gated_delta.py``): cutting into chunks, every chunk's products and
triangular solve, the walk that carries the state; forward, run again under
recomputation and backward; Mosaic calls and XLA operations alike."""

from benchmark import gdn_scopes


def read(ctx):
    return gdn_scopes.scope_ms(ctx, "scan")
