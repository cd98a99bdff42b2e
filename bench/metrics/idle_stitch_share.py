"""Device idle time inside the ``fptc.drain.stitch`` host spans (the
per-strip slicing and copy in ``DecodedBatch.to_host``) over the traced
window, averaged over the cell's chips (program span, on the trace's
clock)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import idle_share  # noqa: E402


def read(run, metric):
    return idle_share(run, metric, "fptc.drain.stitch")
