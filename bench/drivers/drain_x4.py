"""The closed drain loop of ``drain.py`` over every chip of a host: one
process, one ``BatchDecoder(devices="auto")``, the host's chip shares of
the archive (``archive.strips(config, chips)``).

Set-up, window and check are ``drain.py``'s.  The window adds per-chip
counters from its dispatches' ``bucket_pad`` records, keyed by the jax
device id a record names: ``chip<id>_words_padded`` and
``chip<id>_words_live``.  A record without ``device`` (a program from
before the field) adds none.  Before ``drain.py``'s check, the checked
strips are decoded once more on one chip (``devices=None``), and the
strips whose samples differ in any bit from the kept pass's are counted
(``one_chip_mismatch``, printed with the counters; ``correct`` is
``drain.py``'s).
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from fptcbench import spec
from fptcbench.harness import Verdict

_drain = spec.driver("drain")
State = _drain.State
setup = _drain.setup


def chip_words(pads: Iterable[dict]) -> Dict[str, int]:
    """Padded and live words per device of ``bucket_pad`` records."""
    out: Dict[str, int] = {}
    for p in pads:
        if "device" not in p:
            continue
        for name, field in (("words_padded", "words_padded"),
                            ("words_live", "words")):
            key = f"chip{int(p['device'])}_{name}"
            out[key] = out.get(key, 0) + int(p[field])
    return out


def window(ctx, st: State, run) -> None:
    stats = st.decoder.stats
    d0 = stats.dispatches
    _drain.window(ctx, st, run)
    fresh = stats.dispatches - d0
    run.counters.update(
        chip_words(list(stats.bucket_pad)[-fresh:] if fresh else []))


def check(ctx, st: State, run) -> Verdict:
    from repro.serving import BatchDecoder

    arc = st.arc
    picked = [i for i in st.sample if i in st.kept]
    one = BatchDecoder(devices=None).decode(
        [arc.containers[i] for i in picked], arc.tables).to_host()
    run.counters["one_chip_mismatch"] = sum(
        not np.array_equal(st.kept[i], y) for i, y in zip(picked, one))
    return _drain.check(ctx, st, run)
