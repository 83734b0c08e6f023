"""``step_backend_ms`` less ``step_cache_retrieval_ms``: what the backend's
seconds hold outside the persistent cache's retrieval of the executable.  None
in a run where a request missed the cache."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.step_cache_ms("load")
