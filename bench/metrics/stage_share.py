"""Host staging + h2d upload seconds of the engines' pipeline executors
(``ExecutorStats.upload_s``, delta over the window) over window seconds."""


def read(run, metric):
    u = run.counters.get("upload_s")
    return 100.0 * u / run.window_s if u is not None else None
