"""The least time the chip could take for the selective scans' own work in a
step (``benchmark/arithmetic_sambay.py``: three multiply-adds a token, a
channel and a state entry, forward once and backward twice, nothing
recomputed; u, the step, B and C read, y and the block states written, and
their gradients) over ``sscan_scan_ms``.  The count is the algorithm's, from
shapes: it reads the same whatever implements the scan, and LOW by
construction, because the work is the vector unit's and the two peaks are
the MXU's and the HBM's."""

from benchmark import sambay_scopes


def read(ctx):
    return sambay_scopes.scan_roofline(ctx)
