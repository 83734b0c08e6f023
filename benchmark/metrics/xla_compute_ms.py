"""Trace: device time a step of every operation that is neither a Mosaic
call nor a collective."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    return ctx["trace"]["xla_ms_per_step"]
