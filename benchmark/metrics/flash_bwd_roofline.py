"""The least time the chip could take for a step's backward flash calls
(five products a kept pair; q, k, v, o, dO read and dq, dk, dv written:
``benchmark/arithmetic.py``) over the time the trace shows under the flash
kernel's other scopes.  A backward pass that repeats products reads lower,
one that needs fewer calls to do the five reads higher."""

from benchmark import scopes


def read(ctx):
    return scopes.flash_roofline(ctx, "bwd")
