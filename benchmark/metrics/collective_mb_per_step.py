"""Bytes of the collective operations' results in the compiled step's HLO,
a device and a step, in MB (1e6 bytes)."""


def read(ctx):
    if ctx["compiled"] is None:
        return None
    return ctx["compiled"]["collectives"]["bytes"] / 1e6
