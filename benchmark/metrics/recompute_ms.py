"""Trace: self time a step on the ``XLA Ops`` line of the forward work the
backward pass repeats: the operations under ``hvd.loss`` whose ``op_name``
holds JAX's ``rematted_computation`` (each layer's forward, each exit's
head again).  It is part of ``backward_ms``, and of no count of ``mfu``."""

from benchmark import loop_scopes


def read(ctx):
    return loop_scopes.loop_ms(ctx, "recompute")
