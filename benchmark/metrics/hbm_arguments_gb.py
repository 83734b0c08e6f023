"""Bytes of the compiled step's arguments a device (parameters, optimizer
state, batch): ``compiled.memory_analysis()``."""


def read(ctx):
    if ctx["compiled"] is None:
        return None
    return ctx["compiled"]["argument_bytes"] / 1e9
