"""Trace, by the program's scopes: device time a step of the flash kernel's
backward dq call (the Mosaic calls under ``hvd.flash.dq``)."""

from benchmark import scopes


def read(ctx):
    return scopes.flash_ms(ctx, "dq")
