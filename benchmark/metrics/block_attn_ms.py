"""Trace, by the program's scopes: self time a step of the operations under
``hvd.block.attn`` (a layer's mixer block: ``norm_attn``, the projections,
QK-norm, rotation, ``wo`` and the residual add; forward, recomputed and
backward), less the Mosaic calls nested in it (the flash kernel's and the
indexer's have metrics of their own).  A product that XLA fused with this
block's norm counts here (``benchmark/dense_scopes.py``)."""

from benchmark import dense_scopes


def read(ctx):
    return dense_scopes.scope_ms(ctx, "attn")
