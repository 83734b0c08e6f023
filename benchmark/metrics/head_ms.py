"""Trace, by the program's scopes: self time a step of the operations under
``hvd.head``: the final norm (with a looped model's exit gate), the head's
product, the cross-entropy on its logits and their gradient products.  In
the looped cell a part of ``loop_exit_ms``
(``benchmark/dense_scopes.py``)."""

from benchmark import dense_scopes


def read(ctx):
    return dense_scopes.scope_ms(ctx, "head")
