"""The least time the chip could take for a step's ``index_loss`` calls
on the pairs the selection keeps (``benchmark/arithmetic_sparse.py``) over
``index_loss_ms``; the call walks every causal block pair."""

from benchmark import sparse_scopes


def read(ctx):
    return sparse_scopes.call_roofline(ctx, "index_loss")
