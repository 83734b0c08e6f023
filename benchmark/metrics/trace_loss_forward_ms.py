"""The forward half of the program's ``hvd.loss`` span inside the train step's
trace: its flag ``forward_seconds``, stamped between ``jax.vjp`` of the loss
and the pullback's call: the model's Python, ``jax.checkpoint`` staging each
layer, the JVP and the partial evaluation of what it staged."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.loss_half_ms("forward")
