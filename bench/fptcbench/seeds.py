"""Sub-seeds derived from the run's ``--seed`` (any size of integer)."""
from __future__ import annotations

import zlib

import numpy as np


def subseed(seed: int, *tags) -> int:
    """A 63-bit seed for one named use of the run's seed."""
    key = [abs(int(seed)) % (1 << 62), int(seed < 0)]
    for t in tags:
        key.append(zlib.crc32(t.encode()) if isinstance(t, str) else int(t))
    return int(np.random.SeedSequence(key).generate_state(2, np.uint64)[0] >> 1)


def rng(seed: int, *tags) -> np.random.Generator:
    return np.random.default_rng(subseed(seed, *tags))
