"""90th percentile of the intervals between successive step completions in
the window (linear interpolation between closest ranks).  The run prints
the number of intervals on its ``window:`` line."""

import math


def read(ctx):
    ordered = sorted(ctx["intervals_ms"])
    at = (len(ordered) - 1) * 0.9
    low = math.floor(at)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (at - low)
