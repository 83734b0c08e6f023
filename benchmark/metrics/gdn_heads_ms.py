"""Trace, by the program's scopes: self time a step of the operations under
``hvd.gdn.heads``: a gated delta-rule layer's q and k copied from its key
heads to its value heads (two of each here), and the sum over a key head's
copies that is their gradient; forward, recomputed and backward.  What a
rule that indexes key head ``j // 2`` inside its walk would remove."""

from benchmark import gdn_heads_scopes


def read(ctx):
    return gdn_heads_scopes.scope_ms(ctx)
