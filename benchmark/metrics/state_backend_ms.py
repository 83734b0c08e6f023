"""The compile log's ``backend`` records of the benchmark's state program
(``make_state``): XLA's backend compiling it, or fetching it from the persistent
cache and loading it, inside ``state_s``."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.state_ms("backend")
