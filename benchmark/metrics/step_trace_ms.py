"""The program's compile log (``hvd.compile_log()``): what JAX reported for
tracing the train step to a jaxpr, summed over the set-up."""

from benchmark import scopes


def read(ctx):
    return scopes.step_compile_ms("trace")
