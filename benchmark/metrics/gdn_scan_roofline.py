"""The least time the chip could take for the chunked rule's own work in a
step (``benchmark/arithmetic_gdn.py``: the products a chunk and a head of
the published algorithm at chunks of 64, forward once and backward twice,
nothing recomputed; q, k, v and the gates read, o and the chunk states
written) over ``gdn_scan_ms``.  The count is the algorithm's, from shapes:
it reads the same whatever implements the scan."""

from benchmark import gdn_scopes


def read(ctx):
    return gdn_scopes.scan_roofline(ctx)
