"""Programs compiled or loaded inside the window: growth of the step's own
cache (``step._cache_size()``) plus JAX's compile and cache-hit events
there.  0, or ``correct`` is false."""


def read(ctx):
    return ctx["compiles_in_window"]
