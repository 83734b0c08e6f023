"""Trace: self time a step of the Mosaic calls under ``hvd.sparse.index``
(``ops/sparse_index.py::index_loss``: index scores again, the attention
heads' second q k^T for the target, the KL and three gradients)."""

from benchmark import sparse_scopes


def read(ctx):
    return sparse_scopes.scope_ms(ctx, "index_loss")
