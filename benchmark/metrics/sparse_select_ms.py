"""Trace, by the program's scopes: self time a step of the operations under
``hvd.sparse.select``: the Mosaic call that scores a block of queries
against their causal keys in VMEM, finds each query's ``topk``-th largest
score exactly and writes the selection."""

from benchmark import sparse_scopes


def read(ctx):
    return sparse_scopes.scope_ms(ctx, "select")
