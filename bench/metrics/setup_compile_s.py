"""Seconds of XLA backend compiles (or persistent-cache loads) during
set-up, from JAX's ``backend_compile_duration`` events."""


def read(run, metric):
    return run.setup_compile_s
