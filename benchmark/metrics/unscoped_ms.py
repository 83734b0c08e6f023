"""Trace, by the program's scopes: self time a step on the ``XLA Ops`` line
of the operations that are neither a collective nor under any scope of the
program's table: what the scopes miss (copies XLA inserts itself, waits for
asynchronous copies: they carry no ``op_name``)."""

from benchmark import scopes


def read(ctx):
    return scopes.class_ms(ctx, "unscoped")
