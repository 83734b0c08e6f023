"""How many ``mosaic.*`` spans the train step's trace holds
(``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)``): the Mosaic calls the step's
Python bound while JAX traced it (a call whose jitted wrapper JAX had traced
before at the same shape is replayed and binds nothing)."""

from benchmark import startup_spans


def read(ctx):
    binds = startup_spans.kernel_binds()
    return None if binds is None else len(binds)
