"""The LUT dequant and inverse DCT (``batch_decode._decode_bucket_phases``):
the union of the device intervals of the ops in the ``fptc.decode.idct``
named scope over the traced window, averaged over the cell's chips
(device trace)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import scope_share  # noqa: E402


def read(run, metric):
    return scope_share(run, metric, "fptc.decode.idct")
