"""The sensor archive: one container per channel of every record of the
configuration's datasets, calibrated per domain and encoded by the system's
``BatchEncoder`` on the chip; the archive comes from the configuration's
``data_seed``, its order and the checked sample from the run's seed."""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

from . import calib
from . import reference as ref
from .seeds import rng, subseed
from .signals import make_signal

GENERATE_THREADS = 8


@dataclasses.dataclass
class Archive:
    names: List[str]
    domain_ids: List[int]
    lengths: np.ndarray
    ref_tables: Dict[int, ref.RefTables]
    tables: Dict[int, Any]  # the system's DomainTables
    containers: List[Any]  # the system's Containers (host)
    blobs: List[bytes]  # their wire bytes


def strips(config: Dict[str, Any], chips: int) -> List[tuple]:
    """(dataset, length) of every strip: each record of a dataset gives one
    strip per channel and per record length, ``chips`` shares in all."""
    out = []
    for d in config["datasets"]:
        for _ in range(d["records"] * chips):
            out += [(d["name"], n) for n in d["record_samples"]
                    for _ in range(d["channels"])]
    return out


def domain_tables(config: Dict[str, Any], seed: int):
    """(reference tables, system tables) per domain id."""
    samples = config["sizes"]["calibration_samples"]
    rt, pt = {}, {}
    for s in calib.domain_specs(config):
        rt[s.domain_id] = calib.calibrate(s, s.codec, samples, seed)
        pt[s.domain_id] = calib.program_tables(rt[s.domain_id], s.codec)
    return rt, pt


def build(config: Dict[str, Any], chips: int, seed: int, devices,
          spans) -> Archive:
    specs = calib.domain_specs(config)
    dom_of = {nm: s.domain_id for s in specs for nm in s.datasets}
    # every seed gets the same archive (records, samples and tables, all
    # drawn from the configuration's ``data_seed``) in its own order: the
    # content sets the code lengths and the per-word symbol counts, hence
    # the bucket shapes, so a seed that changed the data would change the
    # amount of work
    data = config["sizes"]["data_seed"]
    layout = strips(config, chips)
    order = rng(seed, "order").permutation(len(layout))
    names = [layout[i][0] for i in order]
    lengths = np.array([layout[i][1] for i in order], dtype=np.int64)
    with spans.span("bench.generate"):
        with ThreadPoolExecutor(max_workers=GENERATE_THREADS) as pool:
            signals = list(pool.map(
                lambda i: make_signal(names[i], int(lengths[i]),
                                      seed=subseed(data, "strip", int(order[i]))),
                range(len(layout))))
        rt, pt = domain_tables(config, data)
    from repro.serving import BatchEncoder

    ids = [dom_of[nm] for nm in names]
    with spans.span("bench.ingest"):
        containers = BatchEncoder(devices=devices).encode(
            signals, pt, domain_ids=ids).to_host()
    del signals
    blobs = [c.to_bytes() for c in containers]
    return Archive(names, ids, lengths, rt, pt, containers, blobs)


def sample_strips(arc: Archive, per_dataset: int, seed: int) -> List[int]:
    """``per_dataset`` strips of every dataset drawn from the seed, and the
    longest strip of the archive."""
    r = rng(seed, "sample")
    picked = {int(np.argmax(arc.lengths))}
    for nm in sorted(set(arc.names)):
        mine = [i for i, x in enumerate(arc.names) if x == nm]
        picked.update(int(i) for i in r.permutation(mine)[:per_dataset])
    return sorted(picked)
