"""Cell archive-drain-x4 on the CPU: the cell run tiny over four CPU
devices comes out correct, and not correct with one shard's answer
altered; its two readers on a recorded four-chip trace; and both reading
nothing on a run of a program whose records name no device and which
opens no ``fptc.dispatch`` span.  Nothing here needs a TPU.

JAX may already be running in this process with one device, so the
four-device runs happen in a child process
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) that prints one
JSON line."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from fptcbench import spec  # noqa: E402
from fptcbench.progtrace import ProgramTrace  # noqa: E402
from fptcbench.record import Run  # noqa: E402
from fptcbench.trace import Trace  # noqa: E402

CELL = "archive-drain-x4"
FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_x4.json"
READERS = ("chip_pad_skew.drain4", "idle_dispatch_share.drain4")

CHILD = r'''
import json, sys, time
sys.path[:0] = sys.argv[1:4]
import jax
from _cells import tiny
from fptcbench.harness import run_cell
from repro.serving.batch_decode import BatchDecoder

CELL = "archive-drain-x4"


def run():
    return run_cell(CELL, 31, 1.0, False, t_start=time.perf_counter(),
                    require_chip=False, overrides=tiny(CELL))


sound = run()
orig = BatchDecoder.decode


def shard_one_off(self, containers, tables, **kw):
    """Every bucket decoded on shard 1 comes back one unit off."""
    batch = orig(self, containers, tables, **kw)
    recs = list(self.stats.bucket_pad)[-len(batch._groups):]
    batch._groups = [g + 1.0 if r["shard"] == 1 else g
                     for g, r in zip(batch._groups, recs)]
    return batch


BatchDecoder.decode = shard_one_off
altered = run()
print(json.dumps({"devices": len(jax.devices()), "sound": sound,
                  "altered": altered}))
'''


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT / "src"), str(ROOT / "bench"),
         str(pathlib.Path(__file__).parent)],
        env=env, cwd=str(ROOT), capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_x4_cell_tiny_on_four_devices_is_correct(runs):
    assert runs["devices"] == 4
    r = runs["sound"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"decode_gbps", "setup_s"}


def test_x4_cell_with_one_shard_altered_is_not_correct(runs):
    r = runs["altered"]
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["decode_gap"]["value"] > r["checks"]["decode_gap"]["limit"]


def _fixture_run(program=True, counters=True):
    d = json.loads(FIXTURE.read_text())
    r = Run(cell=CELL, chips=4, seed=1, seconds=1, device={}, peaks={})
    r.trace = Trace.from_json(json.dumps(d["trace"]))
    r.program_trace = (ProgramTrace.from_json(json.dumps(d["program"]))
                       if program else ProgramTrace())
    if counters:
        r.counters.update(d["counters"])
    return r


def _read(name, run):
    return spec.reader(name).read(run, {"name": name})


def test_chip_pad_skew_on_the_four_chip_fixture():
    # chip 0 carries twice the padded words of each other chip: 2 / 1.25
    assert _read("chip_pad_skew.drain4", _fixture_run()) == pytest.approx(60.0)


def test_chip_pad_skew_counts_a_chip_without_work():
    r = Run(cell=CELL, chips=4, seed=1, seconds=1, device={}, peaks={})
    r.counters.update(chip0_words_padded=3, chip1_words_padded=3,
                      chip2_words_padded=6)
    assert _read("chip_pad_skew.drain4", r) == pytest.approx(100.0)


def test_idle_dispatch_share_on_the_four_chip_fixture():
    r = _fixture_run()
    # chips 2 and 3 wait 200 and 400 ns inside the dispatch spans [0, 400]
    assert _read("idle_dispatch_share.drain4", r) == pytest.approx(15.0)
    # chip 3's idle [900, 1000] lies outside them
    assert _read("device_idle.drain", r) == pytest.approx(17.5)


def test_readers_find_nothing_on_a_parent_style_run():
    drv = spec.driver("drain_x4")
    parent_pads = [{"plan_key": (0,), "shard": s, "arm": "pallas",
                    "words": 10, "words_padded": 16} for s in range(4)]
    assert drv.chip_words(parent_pads) == {}
    r = _fixture_run(program=False, counters=False)
    r.counters.update(passes=1, words_live=40, words_padded=64,
                      **drv.chip_words(parent_pads))
    for name in READERS:
        assert _read(name, r) is None, name
    r.trace = None  # and a run without --trace 1
    assert _read("idle_dispatch_share.drain4", r) is None


def test_chip_words_by_device():
    drv = spec.driver("drain_x4")
    pads = [{"device": d, "shard": s, "words": w, "words_padded": p}
            for d, s, w, p in [(0, 0, 5, 8), (3, 1, 9, 16), (0, 2, 3, 4)]]
    assert drv.chip_words(pads) == {
        "chip0_words_padded": 12, "chip0_words_live": 8,
        "chip3_words_padded": 16, "chip3_words_live": 9}
