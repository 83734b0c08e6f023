"""Trace, by the program's scopes: device time a step of the flash kernel's
backward pass (the Mosaic calls under any ``hvd.flash.*`` scope but the
forward call's, however many calls the pass is made of)."""

from benchmark import scopes


def read(ctx):
    return scopes.flash_ms(ctx, "bwd")
