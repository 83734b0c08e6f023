"""The program's span ``import horovod_tpu.jax`` (``hvd.compile_spans()``):
from the first line of the package's ``__init__`` to the last of the JAX
frontend's, on the host clock; a part of ``import_s``, which holds JAX's own
import and the benchmark's too."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.start_ms("IMPORT")
