"""The program's spans inside the train step's trace
(``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)``): the outermost ``hvd.head``
spans, summed: the final norm, the head's product and the cross-entropy as
JAX traced them; a part of ``step_trace_ms``."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.trace_ms("HEAD")
