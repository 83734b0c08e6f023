"""Programs of the set-up that the persistent cache did not hold: JAX's
monitoring events, cache requests less cache hits.  0 after a cell's first
run in a checkout."""


def read(ctx):
    return ctx["cache_misses_in_setup"]
