"""Trace, by the program's scopes: self time a step of the operations under
``hvd.sparse.index``: the indexer's three projections and rotations, their
gradient products, and the Mosaic call that forms the indexer's loss and
the gradients of its queries, key and weights in one walk."""

from benchmark import sparse_scopes


def read(ctx):
    return sparse_scopes.scope_ms(ctx, "index")
