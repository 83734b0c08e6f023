"""The least time the chip could take for the streams' mixes in a step
(``benchmark/arithmetic_hc.py``: a sublayer's forward reads X once for maps
and read and writes ``x_in``, reads X and y and writes X'; the backward by the
same rule; forward once and backward once, nothing recomputed; the
multiply-adds beside them) over ``hc_mix_ms``.  The count is the algorithm's,
from shapes: it reads the same whatever implements the pass, and below 100 %
by the forward pass that ``remat`` runs again."""

from benchmark import hc_scopes


def read(ctx):
    return hc_scopes.mix_roofline(ctx)
