"""Trace: time a step in which a collective runs on a device and no
compute operation does, averaged over the chips."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    return ctx["trace"]["exposed_collective_ms_per_step"]
