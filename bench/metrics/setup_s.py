"""Process start to the first timed call: generation, calibration, archive
encode, warm-up, compiles or compile-cache loads (host clock)."""


def read(run, metric):
    return run.setup_s
