"""Entry module's first line -> JAX, the program and the cell's job and reference imported."""


def read(ctx):
    return ctx["phases"].get("import_s")
