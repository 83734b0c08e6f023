"""The program's compile log (``hvd.compile_log()``): what JAX reported for
lowering the train step's jaxpr to an MLIR module, summed over the set-up."""

from benchmark import scopes


def read(ctx):
    return scopes.step_compile_ms("lower")
