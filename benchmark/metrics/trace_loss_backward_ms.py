"""The rest of the program's ``hvd.loss`` span behind its ``forward_seconds``
stamp: the pullback traced: JAX's transposition, each checkpointed layer
evaluated again, and the program's backward rules (``rule.<op>.bwd``)."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.loss_half_ms("backward")
