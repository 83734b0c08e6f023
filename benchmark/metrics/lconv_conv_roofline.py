"""The least time the chip could take for the gated filters' own work in a
step (``benchmark/arithmetic_lconv.py``: B, C and z read and y written
forward; those and y's cotangent read and three cotangents written backward;
forward once and backward once, nothing recomputed; the elementwise
operations beside them) over ``lconv_conv_ms``.  The count is the
algorithm's, from shapes: it reads the same whatever implements the pass,
and below 100 % by the forward pass that ``remat`` runs again."""

from benchmark import lconv_scopes


def read(ctx):
    return lconv_scopes.conv_roofline(ctx)
