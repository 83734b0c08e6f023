"""The least time the chip could take for a step's flash calls (the larger
of analytic operations / peak FLOP/s and bytes / peak bytes/s,
``benchmark/arithmetic.py``) over the time the trace shows them take."""

from benchmark import arithmetic


def read(ctx):
    work = ctx["job"]["kernel_work_per_step"].get("flash")
    if ctx["trace"] is None or work is None or ctx["peaks"] is None:
        return None
    least_s, bound = arithmetic.roofline_seconds(
        work["flops"], work["bytes"], ctx["peaks"])
    print(f"[benchmark] flash roofline: {bound} bound, least "
          f"{least_s * 1e3:.3f} ms a step", flush=True)
    return 100.0 * least_s * 1e3 / ctx["trace"]["mosaic_ms_per_step"]
