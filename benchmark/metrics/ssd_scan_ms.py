"""Trace, by the program's scopes: self time a step of everything under
``hvd.ssd.scan``, the chunked state-space recurrence (``ops/ssd.py``): the
log-decays, cutting into chunks, every chunk's products, the walk that
carries the state; forward, run again under recomputation and backward;
Mosaic calls and XLA operations alike."""

from benchmark import ssd_scopes


def read(ctx):
    return ssd_scopes.scope_ms(ctx, "scan")
