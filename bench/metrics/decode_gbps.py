"""Reconstructed float32 bytes delivered to host memory per second of the
window, all chips of the process together (host clock)."""


def read(run, metric):
    b = run.counters.get("decoded_bytes")
    return b / run.window_s / 1e9 if b else None
