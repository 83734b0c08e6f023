"""Trace: device time a step of the Mosaic calls (the flash kernel's
forward and backward calls)."""


def read(ctx):
    if ctx["trace"] is None or "flash" not in ctx["job"][
            "kernel_work_per_step"]:
        return None
    return ctx["trace"]["mosaic_ms_per_step"]
