"""The program's spans inside the train step's trace
(``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)``): every ``mosaic.*`` span,
summed, wherever it nests: what binding the Mosaic calls (tracing each
kernel's body to a jaxpr, once a call site) cost the step's trace.  A
cross-cut: the forward calls lie under a block's span, the backward ones
under none, so this is no part of a sum with the ``trace_*`` block metrics."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.total_ms(startup_spans.kernel_binds() or [])
