"""Trace, by the program's scopes: self time a step of the operations under
``hvd.attn.qknorm``: a softmax layer's per-head QK-norm and the rotation of
its q and k; forward, recomputed and backward; Mosaic calls and XLA
operations alike.  A part of ``block_attn_ms`` (its XLA operations) and of
``flash_ms`` (its Mosaic calls)."""

from benchmark import qk_norm_scopes


def read(ctx):
    return qk_norm_scopes.scope_ms(ctx)
