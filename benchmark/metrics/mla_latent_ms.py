"""Trace, by the program's scopes: self time a step of the operations under
``hvd.mla.latent``: latent attention's joint down-projection, the latent's
norm, the up-projection, the rotation of the shared rotary key, its
broadcast over the heads and the concatenation -- what stands between x
and the flash call that a plain attention layer does not have."""

from benchmark import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "latent")
