"""The compile log's ``cache_retrieval`` records of the train step: JAX's event
around getting the executable from the persistent cache (the file's read, and
its deserialisation and load), inside ``step_backend_ms``.  None in a run
where a request missed the cache."""

from benchmark import startup_rules


def read(ctx):
    return startup_rules.step_cache_ms("retrieval")
