"""The Pallas megakernel arm's share of the window (``fused_share.drain``)
on a recorded fixture of a kernel-arm pass, and what the XLA arm's phase
readers read beside it.  Nothing here needs a TPU."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from fptcbench import progtrace, spec  # noqa: E402
from fptcbench.progtrace import ProgramTrace  # noqa: E402
from fptcbench.record import Run  # noqa: E402
from fptcbench.trace import Trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
XLA_PHASES = ("huffman_share.drain", "compact_share.drain", "idct_share.drain")


def _run(fixture, chips=1):
    d = json.loads((FIXTURES / fixture).read_text())
    r = Run(cell="x", chips=chips, seed=1, seconds=1, device={}, peaks={})
    r.trace = Trace.from_json(json.dumps(d["trace"]))
    r.program_trace = ProgramTrace.from_json(json.dumps(d["program"]))
    return r


def _read(name, run):
    return spec.reader(name).read(run, {"name": name})


@pytest.mark.parametrize("chips,share", [(1, 50.0), (2, 25.0)])
def test_fused_share_of_a_kernel_arm_pass(chips, share):
    # the pad, the pallas_call and the slice: [100, 600) of a 1000 ns window
    assert _read("fused_share.drain", _run("trace_fused.json", chips)) == \
        pytest.approx(share)


@pytest.mark.parametrize("name", XLA_PHASES)
def test_xla_phases_read_zero_beside_the_kernel_arm(name):
    assert _read(name, _run("trace_fused.json")) == 0.0


def test_fused_share_reads_nothing_without_the_scope():
    # an XLA-arm pass (the program before the kernel arm had a scope)
    assert _read("fused_share.drain", _run("trace_program.json")) is None
    r = Run(cell="x", chips=1, seed=1, seconds=1, device={}, peaks={})
    assert _read("fused_share.drain", r) is None  # no --trace 1


def test_kernel_arm_op_names_carry_the_scope():
    op = ("jit(_decode_bucket_phases)/fptc.decode.fused/jit(decode_fused)/"
          "pallas_call")
    assert progtrace._SCOPE.search(op).group(1) == "fptc.decode.fused"


def test_fused_share_is_declared_for_the_drain():
    m = {x["name"]: x for x in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]}["fused_share.drain"]
    assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
        "device_trace", "bucket programs", "decode_gbps", ["archive-drain"])
