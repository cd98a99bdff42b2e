"""The benchmark's own calibration of each domain's tables (paper §3.4).

Per domain: DCT coefficients (float64) of calibration strips of the
domain's datasets, per-band scale = the configured percentile of |c| times
the headroom, rounded to float32 as it is shipped; the reference quantiser's
level histogram with one added to every bin.  The system under test gets
these as data (``calibration.tables_from_hist``) and builds its own code
and quantiser from them; the reference builds its own from the same data.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from . import reference as ref
from .signals import make_signal
from .seeds import subseed

__all__ = ["DomainSpec", "calibrate"]


@dataclasses.dataclass(frozen=True)
class DomainSpec:
    domain_id: int
    domain: str
    codec: Dict  # CodecConfig fields
    datasets: Tuple[str, ...]


def domain_specs(cfg: Dict) -> List[DomainSpec]:
    out = []
    for i, d in enumerate(cfg["domains"]):
        out.append(DomainSpec(i, d["domain"], dict(d["codec"]),
                              tuple(d["calibration_datasets"])))
    return out


def calibrate(spec: DomainSpec, codec: Dict, samples: int, seed: int,
              tag: str = "calib") -> ref.RefTables:
    """Reference tables for ``codec`` calibrated on ``samples`` of each of
    the domain's calibration datasets."""
    n, e = codec["n"], codec["e"]
    strips = [make_signal(nm, samples, seed=subseed(seed, tag, spec.domain_id, j))
              for j, nm in enumerate(spec.datasets)]
    coeffs = np.concatenate([ref.coefficients(s, n, e) for s in strips])
    scale = np.percentile(np.abs(coeffs), codec.get("a0_percentile", 99.9), axis=0)
    scale = np.maximum(scale * codec.get("scale_headroom", 1.0), 1e-12)
    scale = scale.astype(np.float32).astype(np.float64)
    t = ref.RefTables(
        domain_id=spec.domain_id, n=n, e=e, l_max=codec.get("l_max", 12),
        b1=codec["b1"], b2=codec["b2"],
        mu=float(np.float32(codec["mu"])),
        alpha1=float(np.float32(codec.get("alpha1", 0.004))),
        scale=scale, hist=np.zeros(256, np.int64),
    )
    hist = np.bincount(ref.quantise(coeffs, t).ravel(), minlength=256) + 1
    return dataclasses.replace(t, hist=hist.astype(np.int64))


def program_tables(t: ref.RefTables, codec: Dict):
    """The system's DomainTables for reference tables ``t``."""
    from repro.core.calibration import tables_from_hist
    from repro.core.config import CodecConfig

    cfg = CodecConfig(**codec)
    return tables_from_hist(cfg, t.scale.astype(np.float32), t.hist,
                            domain_id=t.domain_id)
