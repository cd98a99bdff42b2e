"""The rate of the drain's stitch spans (``stitch_gbps.drain``) on a
recorded fixture of two passes whose ``fptc.drain.stitch`` spans carry the
strips' ``bytes``, and what it reads on a program whose spans carry no
such stat.  Nothing here needs a TPU."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from fptcbench import spec  # noqa: E402
from fptcbench.progtrace import ProgramTrace  # noqa: E402
from fptcbench.record import Run  # noqa: E402
from fptcbench.trace import Trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
NAME = "stitch_gbps.drain"


def _run(fixture):
    d = json.loads((FIXTURES / fixture).read_text())
    r = Run(cell="x", chips=1, seed=1, seconds=1, device={}, peaks={})
    r.trace = Trace.from_json(json.dumps(d["trace"]))
    r.program_trace = ProgramTrace.from_json(json.dumps(d["program"]))
    return r


def _read(run):
    return spec.reader(NAME).read(run, {"name": NAME})


def test_stitch_rate_of_the_spans_inside_the_window():
    # 1000 + 2000 bytes in 50 + 150 ns; the span after the window is left out
    assert _read(_run("trace_stitch.json")) == pytest.approx(15.0)


@pytest.mark.parametrize("fixture", ["trace_program.json", "trace_fused.json"])
def test_stitch_rate_reads_nothing_without_the_bytes_stat(fixture):
    # the program before the stitch span carried its bytes
    assert _read(_run(fixture)) is None


def test_stitch_rate_reads_nothing_untraced():
    r = Run(cell="x", chips=1, seed=1, seconds=1, device={}, peaks={})
    assert _read(r) is None  # no --trace 1
    r.trace = _run("trace_stitch.json").trace
    r.program_trace = ProgramTrace()  # a program without spans
    assert _read(r) is None


def test_stitch_rate_is_declared_for_both_drain_cells():
    m = {x["name"]: x for x in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]}[NAME]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == (
        "GB/s", "higher", "program_span", "drain d2h and stitch",
        "decode_gbps", ["archive-drain", "archive-drain-x4"])
