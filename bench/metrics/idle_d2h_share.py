"""Device idle time inside the ``fptc.drain.d2h`` host spans (the d2h copy
of the padded window tensors, ``fetch_to_host`` in ``DecodedBatch.to_host``)
over the traced window, averaged over the cell's chips (program span, on
the trace's clock).  Prints the spans' ``bytes`` over their time."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import idle_share, span_rate  # noqa: E402


def read(run, metric):
    rate = span_rate(run, "fptc.drain.d2h")
    if rate is not None:
        print(f"[bench] {metric['name']}: d2h {rate} GB/s inside the spans",
              file=sys.stderr)
    return idle_share(run, metric, "fptc.drain.d2h")
