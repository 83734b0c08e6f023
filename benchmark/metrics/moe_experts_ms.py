"""Trace, by the program's scopes: self time a step of the operations under
``hvd.moe.experts`` and of the Mosaic calls XLA:TPU makes of
``jax.lax.ragged_dot`` (named ``ragged-dot-*``, with no scope): the grouped
gate-up and down products over the held experts, the gate between them and
their four gradient products, and what a recomputing backward pass
repeats."""

from benchmark import moe_scopes


def read(ctx):
    return moe_scopes.scope_ms(ctx, "experts")
