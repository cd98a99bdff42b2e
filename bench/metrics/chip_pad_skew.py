"""How far the fullest chip's padded words lie above the mean over the
cell's chips, in % (the window's ``bucket_pad`` records, per device:
``chip<id>_words_padded`` counters of ``drivers/drain_x4.py``).  A chip
with no dispatch counts as 0 words.  Reads nothing where no record names
its device."""
import re

_KEY = re.compile(r"^chip\d+_words_padded$")


def read(run, metric):
    padded = [v for k, v in run.counters.items() if _KEY.match(k)]
    if not padded:
        return None
    mean = sum(padded) / max(run.chips, len(padded))
    return 100.0 * (max(padded) / mean - 1.0)
