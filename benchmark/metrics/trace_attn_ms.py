"""The program's spans inside the train step's trace
(``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)``): the outermost
``hvd.block.attn`` spans, summed: the Python of every layer's mixer block
that ran while JAX traced the step; a part of ``step_trace_ms``."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.trace_ms("BLOCK_ATTN")
