"""JAX's devices found (the TPU runtime coming up), hvd.init() and the mesh built."""


def read(ctx):
    return ctx["phases"].get("backend_s")
