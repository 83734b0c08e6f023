"""Trace, by the program's scopes: self time a step of the operations under
``hvd.block.ffn`` (a layer's feed-forward block: ``norm_mlp``, ``SwiGLU`` or
the routed layer whole, and the residual add; forward, recomputed and
backward).  In a routed cell the Mosaic calls XLA:TPU makes of
``jax.lax.ragged_dot`` (named ``ragged-dot-*``, under no scope) count here,
so the block is whole (``benchmark/dense_scopes.py``)."""

from benchmark import dense_scopes


def read(ctx):
    return dense_scopes.scope_ms(ctx, "ffn")
