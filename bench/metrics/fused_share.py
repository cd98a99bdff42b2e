"""The Pallas megakernel arm of the decode bucket program
(``batch_decode._decode_bucket_phases`` with ``use_kernels``): the union of
the device intervals of the ops in the ``fptc.decode.fused`` named scope
(the ``pallas_call`` and the operand layout around it) over the traced
window, averaged over the cell's chips (device trace).  Reads nothing
where no op carries the scope: every bucket took the XLA arm, or the
program predates the scope."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _phases import scope_share  # noqa: E402
from fptcbench import progtrace  # noqa: E402

SCOPE = "fptc.decode.fused"


def read(run, metric):
    pt = progtrace.for_run(run)
    if pt is None or not any(op[0] == SCOPE for ops in pt.scoped_ops.values()
                             for op in ops):
        return None
    return scope_share(run, metric, SCOPE)
