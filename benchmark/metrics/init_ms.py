"""The program's span ``hvd.init`` (``hvd.compile_spans()``): the common
init (identity, the C++ engine) and the compile cache and log switched on;
a part of ``backend_s``, beside the TPU runtime coming up and the mesh."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.start_ms("INIT")
