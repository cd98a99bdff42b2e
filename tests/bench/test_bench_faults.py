"""A run with its timed path broken underneath comes out not correct: an
answer altered where it is produced, half of a batch left out, and one band
of every window decoded one level off.  The harness's look for a chip is
skipped; everything else of the run is the benchmark's own."""
import numpy as np
import pytest

from _cells import run, tiny


def _alter_samples(out, containers):
    """Every decoded strip gets one sample moved by more than its size."""
    res = []
    for y in out:
        if isinstance(y, np.ndarray) and y.size:
            y = y.copy()
            y[y.size // 2] += 1.0 + 2.0 * abs(float(y[y.size // 2]))
        res.append(y)
    return res


def _half(out, containers):
    return out[: len(out) // 2]


def _one_level(out, containers):
    """In every whole window, the first band past the mu-law zone (the
    first band where there is none) decoded one level above (below, at the
    top level) the level the container holds."""
    from fptcbench import archive, reference as ref
    from fptcbench import spec

    cfg = dict(spec.load_cell("archive-drain").config, **tiny())
    tables, _ = archive.domain_tables(cfg, cfg["sizes"]["data_seed"])
    res = []
    for y, c in zip(out, containers):
        blob = c.to_bytes()
        t = tables[ref.parse(blob).domain_id]
        _, levels = ref.decode_levels(blob, t)
        k = t.b1 if t.b1 < t.e else 0
        w = y.size // t.n
        old = levels[:w, k].astype(np.int64)
        new = np.where(old < 255, old + 1, old - 1)
        step = t.grid()[k, new] - t.grid()[k, old]
        y = y.copy()
        y[: w * t.n] += (step[:, None] * ref.idct_basis(t.n, t.e)[k][None, :]).ravel()
        res.append(y.astype(np.float32))
    return res


FAULTS = {"altered": _alter_samples, "half": _half, "one_level": _one_level}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_drain_fault_is_not_correct(monkeypatch, fault):
    from repro.serving.batch_decode import BatchDecoder

    orig = BatchDecoder.decode

    def broken(self, containers, tables, **kw):
        batch = orig(self, containers, tables, **kw)
        drain = batch.to_host
        batch.to_host = lambda: FAULTS[fault](drain(), containers)
        return batch

    monkeypatch.setattr(BatchDecoder, "decode", broken)
    r = run("archive-drain", 31)
    assert r["correct"] is False, r["checks"]
    if fault == "one_level":
        assert r["checks"]["level_miss"]["value"] > r["checks"]["level_miss"]["limit"]
