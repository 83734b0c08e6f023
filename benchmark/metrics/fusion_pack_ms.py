"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.fusion.pack`` or ``hvd.fusion.unpack`` (the
copies into and out of the fused gradient buffers) and of what an
all-reduce adds that is no collective (under ``hvd.allreduce.<axes>``: an
average's division of the buffer).  The collectives themselves are left
out."""

from benchmark import scopes


def read(ctx):
    return scopes.class_ms(ctx, "packing")
