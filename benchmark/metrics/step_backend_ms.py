"""The program's compile log (``hvd.compile_log()``): what JAX reported for
XLA's backend compiling the train step, or fetching it from the persistent cache, summed over the set-up."""

from benchmark import scopes


def read(ctx):
    return scopes.step_compile_ms("backend")
