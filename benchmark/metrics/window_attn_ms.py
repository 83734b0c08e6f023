"""Trace, by the program's scopes: self time a step of everything under
``hvd.attn.window``, a sliding-window layer's attention
(``models/llama.py::LlamaAttention`` where the layer has a window): the
rotation of its q and k and the two flash calls over the band; forward, run
again under recomputation and backward; Mosaic calls and XLA operations
alike."""

from benchmark import window_scopes


def read(ctx):
    return window_scopes.scope_ms(ctx, "window")
