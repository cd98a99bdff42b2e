"""Reads ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by name:

  bench/configs/<config>.json     the deployment (sizes, codec, guarantees)
  bench/traffic/<traffic>.json    the mix; ``driver`` names its generator
  bench/drivers/<driver>.py       a loop kind: set-up, window, check
  bench/metrics/<metric>.py       one reader per metric (``read(run)``);
                                  ``a.b`` falls back to ``a.py``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_benchmark(path: Optional[pathlib.Path] = None) -> Dict[str, Any]:
    return json.loads((path or ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def _load_file(path: pathlib.Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return _load_file(BENCH / "drivers" / f"{name}.py", f"fptc_driver_{name}")


def reader(metric: str):
    """The reader of ``metric``: ``metrics/<metric>.py``, else the file of
    the part before the first dot (one reader serving every suffix)."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            return _load_file(path, "fptc_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {metric!r}")
