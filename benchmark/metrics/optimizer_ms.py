"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.optimizer`` or ``hvd.apply``: the wrapped
optax update (master weights included) and writing the parameters, as far
as XLA left them fusions of their own (see ``backward_ms``)."""

from benchmark import scopes


def read(ctx):
    return scopes.class_ms(ctx, "optimizer")
