"""The SELF time of the program's ``hvd.loss`` span inside the train step's
trace (``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)``): what ran under
``jax.value_and_grad(loss_fn)`` and under no other span of the program's:
JAX's linearisation and transposition of the loss, ``jax.checkpoint``'s and
``scan``'s own staging, the embedding, the model's top level."""

from benchmark import startup_spans


def read(ctx):
    return startup_spans.trace_self_ms("LOSS")
