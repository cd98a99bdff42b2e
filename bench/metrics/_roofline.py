"""Shared arithmetic of the roofline readers."""
import sys

from fptcbench.work import least_time

# the jitted program whose events hold each family's device work; the name
# encloses the XLA arm and the Pallas kernel alike
PROGRAMS = {"decode": "_decode_bucket"}


def share(run, metric, family):
    if run.trace is None or family not in run.work:
        return None
    ids = [d for d in range(run.chips)]
    t = run.trace.program_s(PROGRAMS[family], ids)
    if t <= 0:
        return None
    least, bound = least_time(*run.work[family], run.peaks)
    print(f"[bench] {metric['name']}: bound={bound} least_s={least} "
          f"program_s={t}", file=sys.stderr)
    return 100.0 * least / t
