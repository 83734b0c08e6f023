"""The least time the chip could take for the dense products the model
needs in a step (three products a weight of the layers' projections and
FFN and of the head, times the passes of a looped model; from the
configuration and ``benchmark/arithmetic.py``, never from the trace, so
recomputed work cannot raise it) over ``block_attn_ms + block_ffn_ms +
head_ms``.  No number where a matrix product of the loss lies in no block
(``benchmark/dense_scopes.py``)."""

from benchmark import dense_scopes


def read(ctx):
    return dense_scopes.dense_roofline(ctx)
