"""The least time the chip could take for a step's grouped products over
the held experts at the rows they expect (six products a routed layer, the
experts' weights touched once a product and the rows on both sides:
``benchmark/arithmetic_moe.py``) over ``moe_experts_ms``."""

from benchmark import moe_scopes


def read(ctx):
    return moe_scopes.experts_roofline(ctx)
