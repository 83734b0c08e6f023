"""Trace, by the program's scopes: self time a step of the operations under
``hvd.gdn.solve``: the inverse of every chunk's unit lower-triangular system
in a gated delta-rule layer and its transpose; forward, recomputed and
backward; the Mosaic call and what XLA does around it.  A part of
``gdn_scan_ms``."""

from benchmark import gdn_solve_scopes


def read(ctx):
    return gdn_solve_scopes.scope_ms(ctx)
