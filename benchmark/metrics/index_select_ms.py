"""Trace: self time a step of the Mosaic calls under ``hvd.sparse.select``
(``ops/sparse_index.py::select_keys``)."""

from benchmark import sparse_scopes


def read(ctx):
    return sparse_scopes.scope_ms(ctx, "index_select")
