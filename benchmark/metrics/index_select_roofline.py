"""The least time the chip could take for a step's ``select_keys`` calls
(the index scores of every causal pair on the MXU; q_I, k_I, w read and a
byte a pair written: ``benchmark/arithmetic_sparse.py``) over
``index_select_ms``.  Comparing and counting are no operations of the
MXU's, so this share says how far the selection is from free."""

from benchmark import sparse_scopes


def read(ctx):
    return sparse_scopes.call_roofline(ctx, "index_select")
