"""The SELF time of the ``layer.*`` spans in the train step's trace, summed:
what JAX did for a layer outside the program's Python while the layer's call
was open (``jax.checkpoint`` tracing and staging it, its JVP, the partial
evaluation of its jaxpr)."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.prefix_self_ms("LAYER")
