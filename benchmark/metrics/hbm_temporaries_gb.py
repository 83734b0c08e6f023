"""Bytes of the compiled step's temporaries a device (activations kept for
the backward pass, gradients): ``compiled.memory_analysis()``."""


def read(ctx):
    if ctx["compiled"] is None:
        return None
    return ctx["compiled"]["temporary_bytes"] / 1e9
