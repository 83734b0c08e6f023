"""``trace_loss_backward_ms`` less every span that began in the backward half
of ``hvd.loss``: transposition that no rule of the program's asked for."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.loss_half_ms("backward_self")
