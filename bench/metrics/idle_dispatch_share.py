"""Device idle time inside the ``fptc.dispatch`` host spans (the enqueue
of one bucket program in ``BatchDecoder.decode_streams``, one span per
(group, shard)) over the traced window, averaged over the cell's chips
(program span, on the trace's clock).  Reads nothing where the program
opens no such span."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import idle_share  # noqa: E402


def read(run, metric):
    return idle_share(run, metric, "fptc.dispatch")
