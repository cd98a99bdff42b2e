"""Device idle time inside the ``fptc.stage`` host spans (host concat and
h2d put of one bucket in ``PipelineExecutor.run``, on whichever thread runs
it) over the traced window, averaged over the cell's chips (program span,
on the trace's clock)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import idle_share  # noqa: E402


def read(run, metric):
    return idle_share(run, metric, "fptc.stage")
