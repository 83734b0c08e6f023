"""The tiny sizes of the jobs that came after ``tiny_sizes.py``: added to
its table here, before the test modules import the same module object."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tiny_sizes import TINY  # noqa: E402

# The tiny decoder's sizes; the configuration's own total_ut_steps (4) and
# remat stay.  Four passes round a little more than one in bf16.
TINY.setdefault("looped_lm", {
    "config": {**TINY["decoder_lm"]["config"],
               "checks": {**TINY["decoder_lm"]["config"]["checks"],
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.06}}},
    "traffic": dict(TINY["decoder_lm"]["traffic"]),
})
