"""First line of the entry module -> start of the window: the sum of the
five phases."""


def read(ctx):
    return ctx["setup_s"]
