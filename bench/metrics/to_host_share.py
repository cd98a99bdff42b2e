"""Host seconds inside ``to_host()`` (the benchmark's ``bench.to_host``
span: the d2h copy and the per-strip stitch, opened once the device program
has finished, ``bench.wait``) over window seconds."""


def read(run, metric):
    t = run.span_total("bench.to_host")
    return 100.0 * t / run.window_s if t > 0 else None
