"""Trace, by the program's scopes: device time a step of the flash kernel's
forward call (the Mosaic calls under ``hvd.flash.fwd``)."""

from benchmark import scopes


def read(ctx):
    return scopes.flash_ms(ctx, "fwd")
