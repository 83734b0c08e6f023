"""The least time the chip could take for the chunked rule's own work in a
step (``benchmark/arithmetic_kda.py``: the products a chunk and a head of the
algorithm at chunks of 64 with a decay a channel, forward once and backward
twice, nothing recomputed; q, k, v, g and beta read, o and the chunk states
written) over ``kda_scan_ms``.  The count is the algorithm's, from shapes: it
reads the same whatever implements the scan."""

from benchmark import kda_scopes


def read(ctx):
    return kda_scopes.scan_roofline(ctx)
