"""The program's spans inside the train step's trace
(``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)``): ``hvd.optimizer``,
``hvd.apply`` and ``hvd.aux_allreduce``, summed: the gradients' all-reduces,
the optax update and its application as JAX traced them; a part of
``step_trace_ms``, outside ``hvd.loss``."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.trace_ms("OPTIMIZER", "APPLY", "AUX_ALLREDUCE")
