"""Trace: 1 - union of the device operations' intervals / the window of
whole traced steps, averaged over the chips, in percent."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["trace"]["window_s"])
