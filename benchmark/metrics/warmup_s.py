"""The fixed number of blocked warm-up steps, up to the start of the window."""


def read(ctx):
    return ctx["phases"].get("warmup_s")
