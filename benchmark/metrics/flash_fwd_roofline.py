"""The least time the chip could take for a step's forward flash calls (two
products a kept pair; q, k, v read and o written: ``benchmark/arithmetic.py``)
over the time the trace shows under ``hvd.flash.fwd``."""

from benchmark import scopes


def read(ctx):
    return scopes.flash_roofline(ctx, "fwd")
