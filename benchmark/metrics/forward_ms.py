"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.loss`` and no ``transpose(...)``: the forward
pass, the flash kernel's forward call included."""

from benchmark import scopes


def read(ctx):
    return scopes.class_ms(ctx, "forward")
