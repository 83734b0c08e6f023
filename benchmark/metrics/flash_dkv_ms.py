"""Trace, by the program's scopes: device time a step of the flash kernel's
backward dk and dv call (the Mosaic calls under ``hvd.flash.dkv``)."""

from benchmark import scopes


def read(ctx):
    return scopes.flash_ms(ctx, "dkv")
