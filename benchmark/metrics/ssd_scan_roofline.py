"""The least time the chip could take for the chunked scan's own work in a
step (``benchmark/arithmetic_ssd.py``: the products a chunk and a head of the
published algorithm at chunks of 128, C B^T once a group, forward once and
backward twice, nothing recomputed; u, B, C and dt read, y and the chunk
states written) over ``ssd_scan_ms``.  The count is the algorithm's, from
shapes: it reads the same whatever implements the scan."""

from benchmark import ssd_scopes


def read(ctx):
    return ssd_scopes.scan_roofline(ctx)
