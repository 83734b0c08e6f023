"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations under ``hvd.loss`` and a ``transpose(...)``: the backward
pass, the flash kernel's backward calls included.  A weight-gradient
matmul into which XLA fused the optimizer's update counts here: the
fusion has one ``op_name``, its root's."""

from benchmark import scopes


def read(ctx):
    return scopes.class_ms(ctx, "backward")
