"""The compaction (``symlen.compact_padded_scatter``: prefix sum and
scatter): the union of the device intervals of the ops in the
``fptc.decode.compact`` named scope over the traced window, averaged over
the cell's chips (device trace)."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import scope_share  # noqa: E402


def read(run, metric):
    return scope_share(run, metric, "fptc.decode.compact")
