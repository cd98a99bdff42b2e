"""The small sizes at which the benchmark's cells run on the CPU in tests."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

CELLS = ["archive-drain"]


def tiny(cell="archive-drain"):
    """Configuration overrides: one short record of every dataset (two
    channels for the first, of different lengths) and small calibration."""
    from fptcbench import spec

    cfg = spec.load_cell(cell).config
    datasets = [dict(d, records=1, channels=2 if i == 0 else 1,
                     record_samples=[2048 + 1024 * (i % 3) + 7])
                for i, d in enumerate(cfg["datasets"])]
    sizes = dict(cfg["sizes"], calibration_samples=4096,
                 check_strips_per_dataset=1)
    return {"datasets": datasets, "sizes": sizes}


def run(cell, seed):
    import time

    from fptcbench.harness import run_cell

    return run_cell(cell, seed, 1.0, False, t_start=time.perf_counter(),
                    require_chip=False, overrides=tiny(cell))
