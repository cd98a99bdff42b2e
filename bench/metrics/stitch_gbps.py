"""Rate of the ``fptc.drain.stitch`` host spans (``DecodedBatch.to_host``
handing each strip back after the d2h copy): the spans' ``bytes`` stat,
the strips' sample bytes, over their summed duration, for the spans inside
the traced window, in GB/s (program span, on the trace's clock).  Reads
nothing where the spans carry no ``bytes`` stat (a program that predates
it) or the run was not traced."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import span_rate  # noqa: E402


def read(run, metric):
    return span_rate(run, "fptc.drain.stitch")
