"""Peak bytes in use + reserved on the fullest chip, read when the window
ends and before the reference comparison, in GB (1e9 bytes)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
