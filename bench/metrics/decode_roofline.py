"""Least time of the decode bucket programs' work (live words, windows, N,
E; ``fptcbench.work``) over their device time in the trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _roofline import share  # noqa: E402


def read(run, metric):
    return share(run, metric, "decode")
