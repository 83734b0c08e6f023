"""The compile log's ``trace`` records of the benchmark's state program
(``make_state``: parameters, optimizer state and the batch pool from the
seed), inside ``state_s``."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.state_ms("trace")
