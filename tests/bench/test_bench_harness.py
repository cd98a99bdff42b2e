"""The chip benchmark's harness on the CPU: its files, its trace reduction
and readers on a recorded fixture, its work count, its generators and its
reference codec.  Nothing here needs a TPU."""
import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from fptcbench import reference as ref  # noqa: E402
from fptcbench import spec, work  # noqa: E402
from fptcbench.record import Run, Spans  # noqa: E402
from fptcbench.signals import DATASETS, make_signal  # noqa: E402
from fptcbench.trace import Trace  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_small.json"


def test_benchmark_names_and_units():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    drv = spec.driver(c.traffic["driver"])
    for fn in ("setup", "window", "check"):
        assert callable(getattr(drv, fn))
    assert c.traffic["limits"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m["name"]).read)
    for m in c.per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_hold_the_codec(config):
    from repro.core.config import DOMAIN_DEFAULTS, CodecConfig

    cfg = json.loads((ROOT / config["file"]).read_text())
    assert cfg["reduced"] == config["reduced"]
    for d in cfg["datasets"]:
        cut = d["records"] != d["published"]["records"]
        if d["published"]["records"] is not None:
            assert cut == (f"datasets.{d['name']}.records" in cfg["reduced"])
        assert d["name"] in DATASETS
    for d in cfg["domains"]:
        assert CodecConfig(**d["codec"]) == DOMAIN_DEFAULTS[d["domain"]]
        assert all(nm in DATASETS for nm in d["calibration_datasets"])


def _trace():
    return Trace.from_json(FIXTURE.read_text())


def test_trace_busy_idle_and_program_time():
    t = _trace()
    assert t.busy_intervals(0) == [(100.0, 400.0), (600.0, 700.0)]
    assert t.busy_s(0) == pytest.approx(4e-7)
    assert t.busy_s(1) == pytest.approx(1e-6)  # clipped to the window
    assert t.window_s == pytest.approx(1e-6)
    assert t.program_s("_decode_bucket", [0]) == pytest.approx(3e-7)
    assert t.program_s("_decode_bucket", [0, 1]) == pytest.approx(1.3e-6)
    assert t.program_s("_encode_bucket", [0]) == pytest.approx(1e-7)


def test_trace_top_ops_and_idle_gaps_by_host_span():
    t = _trace()
    top = t.top_ops([0])
    assert [n for n, _ in top] == ["fusion", "copy"]
    assert [s for _, s in top] == pytest.approx([3e-7, 1.5e-7])
    gaps = t.idle_gaps(0)
    assert [n for n, _ in gaps] == ["bench.decode", "bench.to_host", "bench.decode"]
    assert [s for _, s in gaps] == pytest.approx([3e-7, 2e-7, 1e-7])


def _run(chips=1):
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    r = Run(cell="x", chips=chips, seed=1, seconds=1, device={}, peaks=peaks)
    r.trace = _trace()
    return r


def test_trace_readers():
    r = _run()
    assert spec.reader("device_idle.drain").read(r, {}) == pytest.approx(60.0)
    r2 = _run(chips=2)  # the idle share averages over the cell's chips
    assert spec.reader("device_idle.drain").read(r2, {}) == pytest.approx(30.0)
    # memory-bound work of 1.5e-7 s at 819 GB/s over 3e-7 s of program time
    r.add_work("decode", 0.0, 819e9 * 1.5e-7)
    m = {"name": "decode_roofline.drain"}
    assert spec.reader(m["name"]).read(r, m) == pytest.approx(50.0)
    assert spec.reader(m["name"]).read(_run(), m) is None  # no work counted


def test_counter_readers():
    r = _run()
    r.window_t0, r.window_t1 = 10.0, 12.0
    r.counters.update(decoded_bytes=4e9, upload_s=0.5, words_live=75,
                      words_padded=100)
    read = lambda n: spec.reader(n).read(r, {"name": n})
    assert read("decode_gbps") == pytest.approx(2.0)
    assert read("stage_share.drain") == pytest.approx(25.0)
    assert read("word_pad_share.drain") == pytest.approx(25.0)
    r.spans = Spans()
    r.spans.items = [("bench.to_host", 9.0, 10.5), ("bench.to_host", 11.5, 12.5),
                     ("bench.decode", 10.5, 11.5)]
    assert read("to_host_share.drain") == pytest.approx(50.0)  # clipped


def test_work_count_matches_a_hand_count():
    # 10 windows of N=32 with E=6, 7 words: iDCT 2*10*6*32 flops; 7 words
    # of 8 bytes plus a one-byte sidecar each, 10*32 float32 samples out
    assert work.decode_work(7, 10, 32, 6) == (3840.0, 7 * 9 + 10 * 32 * 4)
    peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e6}
    assert work.least_time(3840.0, 1343.0, peaks) == (3.84, "compute")
    from repro.serving import BatchEncoder
    from fptcbench import archive

    rt, pt = archive.domain_tables(spec.load_cell("archive-drain").config, 3)
    c = BatchEncoder(devices=None).encode([make_signal("load_power", 320, 5)],
                                          pt[2]).to_host()[0]
    f, b = work.decode_work(c.num_words, c.num_windows, c.n, c.e)
    assert (c.num_windows, c.e) == (10, 6)
    assert f == 3840.0 and b == 9 * c.num_words + 1280


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "archive-drain", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_generators_equal_the_repository_generators(name):
    from repro.data.signals import make_signal as program_signal

    for n in (256, 1000, 4097):
        a, b = make_signal(name, n, seed=n + 11), program_signal(name, n, seed=n + 11)
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("domain_id", [0, 1, 2, 3])
def test_reference_codec_reads_the_system(domain_id):
    """The reference's own code lengths, parser and decoder agree with the
    system's host codec at a small size."""
    from repro.core import codec
    from fptcbench import archive

    cfg = spec.load_cell("archive-drain").config
    rt, pt = archive.domain_tables(dict(cfg, sizes=dict(cfg["sizes"], calibration_samples=8192)), 9)
    t, p = rt[domain_id], pt[domain_id]
    assert np.array_equal(t.lengths(), p.book.lengths)
    assert np.max(np.abs(t.grid() - np.asarray(p.quant.grid))) <= 1e-6 * t.scale.max()
    name = cfg["domains"][domain_id]["calibration_datasets"][0]
    x = make_signal(name, 3000, seed=4)
    c = codec.encode(x, p)
    parsed, levels = ref.decode_levels(c.to_bytes(), t)
    assert parsed.signal_length == 3000
    # a level may differ only where float32 and float64 coefficients fall
    # on two sides of a cell edge
    assert np.mean(levels != ref.quantise(ref.coefficients(x, t.n, t.e), t)) < 1e-3
    gap = np.max(np.abs(codec.decode(c, p) - ref.decode(c.to_bytes(), t)))
    assert gap <= 1e-6 * t.scale.max()


def test_reference_rejects_a_damaged_container():
    from repro.core import codec
    from fptcbench import archive

    cfg = spec.load_cell("archive-drain").config
    rt, pt = archive.domain_tables(dict(cfg, sizes=dict(cfg["sizes"], calibration_samples=4096)), 2)
    blob = bytearray(codec.encode(make_signal("seismic", 2000, 1), pt[1]).to_bytes())
    blob[60] ^= 0xFF
    with pytest.raises(ref.RefFormatError):
        ref.decode_levels(bytes(blob), rt[1])


def test_archive_layout_follows_the_records():
    from fptcbench import archive

    cfg = spec.load_cell("archive-drain").config
    one = archive.strips(cfg, 1)
    by = {d["name"]: d for d in cfg["datasets"]}
    for nm, d in by.items():
        mine = [n for x, n in one if x == nm]
        assert len(mine) == d["records"] * d["channels"] * len(d["record_samples"])
        assert sorted(set(mine)) == sorted(set(d["record_samples"]))
    assert len(archive.strips(cfg, 4)) == 4 * len(one)
    mit = by["mitbih"]
    assert (mit["channels"], mit["record_samples"]) == (2, [650000])


def test_sample_holds_every_dataset_and_the_longest():
    from fptcbench import archive

    names = ["a", "a", "b", "c", "c", "c"]
    arc = archive.Archive(names, [0] * 6, np.array([5, 9, 3, 4, 4, 1]),
                          {}, {}, [], [])
    for seed in (1, 2**31 + 9):
        pick = archive.sample_strips(arc, 1, seed)
        assert 1 in pick and {names[i] for i in pick} == {"a", "b", "c"}
        assert len(pick) <= 4


@pytest.mark.parametrize("domain_id", [0, 1, 2, 3])
def test_level_miss_counts_one_level_off(domain_id):
    from fptcbench import archive

    cfg = spec.load_cell("archive-drain").config
    rt, _ = archive.domain_tables(dict(cfg, sizes=dict(cfg["sizes"], calibration_samples=4096)), 5)
    t = rt[domain_id]
    name = cfg["domains"][domain_id]["calibration_datasets"][0]
    levels = ref.quantise(ref.coefficients(make_signal(name, 3200, 8), t.n, t.e), t)
    y = ref.reconstruct(levels, t, 3200).astype(np.float32)
    assert ref.level_miss(y, levels, t) == (0, 100 * t.e)
    k = t.b1 if t.b1 < t.e else 0
    off = levels.copy()
    off[:, k] = np.where(off[:, k] < 255, off[:, k] + 1, off[:, k] - 1)
    misses, compared = ref.level_miss(ref.reconstruct(off, t, 3200), levels, t)
    assert misses == 100 and compared == 100 * t.e
