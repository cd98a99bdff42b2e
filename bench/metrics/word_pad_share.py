"""Padding words as a share of the padded words the decode buckets of the
window carried (``BatchDecoderStats.bucket_pad``)."""


def read(run, metric):
    padded = run.counters.get("words_padded")
    if not padded:
        return None
    return 100.0 * (padded - run.counters["words_live"]) / padded
