"""Model FLOP/s utilisation: analytic forward + backward operations an
image (``benchmark/arithmetic.py``; convolutions and the classifier) x
images/s/chip over the chip's published bf16 peak."""

from benchmark import arithmetic


def read(ctx):
    if ctx["peaks"] is None or ctx["job"]["unit"] != "images":
        return None
    return arithmetic.utilisation_percent(
        ctx["job"]["flops_per_unit"], ctx["units_per_s_per_chip"],
        ctx["peaks"])
