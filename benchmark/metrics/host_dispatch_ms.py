"""Median host time of one un-blocked call of the step in the window."""

import statistics


def read(ctx):
    return statistics.median(ctx["dispatch_ms"])
