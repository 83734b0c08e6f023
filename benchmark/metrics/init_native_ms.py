"""The program's span ``hvd.init.native`` (``hvd.compile_spans()``), inside
``hvd.init``: the C++ engine found (or built), loaded and started, which no
jitted program calls."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.start_ms("INIT_NATIVE")
