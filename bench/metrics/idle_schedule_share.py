"""Device idle time inside the ``fptc.schedule`` host spans (bucketing,
cost-model costs, member positions and the lazy stagers in
``BatchDecoder.decode``, before the executor runs) over the traced window,
averaged over the cell's chips (program span, on the trace's clock)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import idle_share  # noqa: E402


def read(run, metric):
    return idle_share(run, metric, "fptc.schedule")
