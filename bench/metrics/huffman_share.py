"""The Huffman slot scan (``symlen.unpack_symlen``'s ``lax.scan``): the
union of the device intervals of the ops in the ``fptc.decode.huffman``
named scope over the traced window, averaged over the cell's chips
(device trace).  Prints the share of the decode bucket program's device
time that lies in none of the three ``fptc.decode`` scopes."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import scope_share  # noqa: E402


def read(run, metric):
    return scope_share(run, metric, "fptc.decode.huffman")
