"""The SELF time of the ``rule.*`` spans in the train step's trace, summed:
a ``custom_vjp``'s forward and backward rules outside the scopes they enter,
and what JAX ran because a rule asked (``jax.vjp`` of a routed layer's buffer
body and its pullback's call, inside ``rule._live_buffers.bwd``)."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.prefix_self_ms("RULE")
