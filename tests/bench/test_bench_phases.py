"""The program's spans and scopes in the trace reduction
(``fptcbench.progtrace``) and the phase and idle readers built on it, on a
recorded fixture and on a small trace file written here in the profiler's
own format.  Nothing here needs a TPU."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from fptcbench import progtrace, spec  # noqa: E402
from fptcbench import trace as tr  # noqa: E402
from fptcbench.progtrace import ProgramTrace  # noqa: E402
from fptcbench.record import Run  # noqa: E402
from fptcbench.trace import Trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
SCOPE_METRICS = {"huffman_share.drain": 30.0, "compact_share.drain": 15.0,
                 "idct_share.drain": 10.0}
IDLE_METRICS = {"idle_schedule_share.drain": 6.0,
                "idle_stage_share.drain": 6.0,
                "idle_d2h_share.drain": 10.0,
                "idle_stitch_share.drain": 15.0}


def _fixture():
    d = json.loads((FIXTURES / "trace_program.json").read_text())
    return (Trace.from_json(json.dumps(d["trace"])),
            ProgramTrace.from_json(json.dumps(d["program"])))


def _run(chips=1, program=True):
    r = Run(cell="x", chips=chips, seed=1, seconds=1, device={}, peaks={})
    r.trace, pt = _fixture()
    r.program_trace = pt if program else ProgramTrace()
    return r


def _read(name, run):
    return spec.reader(name).read(run, {"name": name})


def test_program_fixture_reduction():
    t, pt = _fixture()
    lo, hi = t.window
    assert pt.span_intervals("fptc.stage", lo, hi) == [(40.0, 120.0),
                                                       (300.0, 350.0)]
    # the d2h span after the window is clipped away
    assert pt.span_intervals("fptc.drain.d2h", lo, hi) == [(700.0, 800.0)]
    assert pt.scope_intervals(0, ("fptc.decode.huffman",), lo, hi) == [
        (100.0, 400.0)]  # the while body's op nests in the while
    assert pt.scope_intervals(0, progtrace.SCOPES, lo, hi) == [(100.0, 650.0)]
    assert pt.scope_intervals(1, progtrace.SCOPES, lo, hi) == []
    assert ProgramTrace.from_json(pt.to_json()) == pt


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS))
def test_scope_share_readers(name, capsys):
    assert _read(name, _run()) == pytest.approx(SCOPE_METRICS[name])
    # 550 of the program's 600 busy ns are scoped; the copy is not
    assert "in no scope [8.333" in capsys.readouterr().err
    # averaged over the cell's chips: the second chip ran nothing
    assert _read(name, _run(chips=2)) == pytest.approx(SCOPE_METRICS[name] / 2)


@pytest.mark.parametrize("name", sorted(IDLE_METRICS))
def test_idle_share_readers(name):
    assert _read(name, _run()) == pytest.approx(IDLE_METRICS[name])


def test_idle_shares_stay_within_device_idle():
    r = _run()
    idle = _read("device_idle.drain", r)
    assert idle == pytest.approx(40.0)
    # fptc.schedule and fptc.stage overlap on [40, 60], so the four
    # shares sum to 37 points and their union covers 35 of the 40
    assert sum(_read(n, r) for n in IDLE_METRICS) == pytest.approx(37.0)


def test_d2h_reader_reports_the_span_rate(capsys):
    _read("idle_d2h_share.drain", _run())
    # 1000 bytes in 100 ns inside the window (the later span lies outside)
    assert "d2h 10.0 GB/s" in capsys.readouterr().err


def test_old_fixture_loads_and_readers_find_nothing():
    t = Trace.from_json((FIXTURES / "trace_small.json").read_text())
    assert t.window_s == pytest.approx(1e-6)
    r = Run(cell="x", chips=1, seed=1, seconds=1, device={}, peaks={})
    for name in list(SCOPE_METRICS) + list(IDLE_METRICS):
        assert _read(name, r) is None  # no --trace 1
    r.trace = t
    r.program_trace = ProgramTrace()  # a program without spans or scopes
    for name in list(SCOPE_METRICS) + list(IDLE_METRICS):
        assert _read(name, r) is None


def test_instruction_name_of_an_op_event():
    assert progtrace.instruction("%fusion.62 = s32[8]{0} fusion(%a)") == "fusion.62"
    assert progtrace.instruction("%sort = (s32[4]{0}) sort(%b)") == "sort"


# -- a trace file in the profiler's own format ---------------------------------
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num, v):
    return _varint(num << 3) + _varint(v)


def _msg(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _instr(name, op_name=None):
    meta = _msg(7, _msg(2, op_name)) if op_name else b""
    return _msg(2, _msg(1, name) + _msg(2, "fusion") + meta)


def _hlo_proto():
    main = _msg(1, "main") + b"".join([
        _instr("while.4", "jit(f)/fptc.decode.huffman/while"),
        _instr("sort", "jit(f)/fptc.decode.compact/scatter"),
        _instr("fusion.2", "jit(f)/fptc.decode.compact/scatter"),
        _instr("fusion.1", "jit(f)/fptc.decode.idct/gather"),
        _instr("copy-start"),
        _instr("fusion.3", "jit(f)/convert_element_type"),
    ])
    body = _msg(1, "body.1") + _instr(
        "fusion.62", "jit(f)/fptc.decode.huffman/while/body/gather")
    module = _msg(1, "jit_f") + _msg(3, main) + _msg(3, body)
    return _msg(1, module)


def _plane(pid, name, lines, event_names, stat_names=(), metadata_stats=None):
    out = _int(1, pid) + _msg(2, name)
    for line in lines:
        out += _msg(3, line)
    for i, n in enumerate(event_names, 1):
        md = _int(1, i) + _msg(2, n)
        for stat in (metadata_stats or {}).get(n, ()):
            md += _msg(5, stat)
        out += _msg(4, _int(1, i) + _msg(2, md))
    for i, n in enumerate(stat_names, 1):
        out += _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, n)))
    return out


def _line(lid, name, events):
    """events: (metadata id, start ns, duration ns, stats bytes)."""
    out = _int(1, lid) + _msg(2, name) + _int(3, 0)
    for md, s, d, stats in events:
        out += _msg(4, _int(1, md) + _int(2, int(s * 1000)) +
                    _int(3, int(d * 1000)) + stats)
    return out


OPS = ["%while.4 = (s32[]) while(s32[] %t)", "%fusion.62 = s32[64]{0} fusion()",
       "%sort = (s32[512]{0}) sort(s32[512]{0} %b)",
       "%fusion.2 = u8[256]{0} fusion()", "%fusion.1 = f32[256]{0} fusion()",
       "%copy-start = (f32[16,256]{1,0}) copy-start()",
       "%fusion.3 = s32[8]{0} fusion()"]


def _xspace():
    module = "jit__decode_bucket_phases(7)"
    ops = [(1, 100, 300), (2, 150, 100), (3, 400, 100), (4, 500, 50),
           (5, 550, 100), (6, 650, 50), (7, 800, 20)]  # the last: no module
    device = _plane(
        2, "/device:TPU:0",
        [_line(1, "XLA Modules", [(8, 100, 600, b"")]),
         _line(2, "XLA Ops", [(m, s, d, b"") for m, s, d in ops])],
        OPS + [module])
    bytes_stat = _msg(4, _int(1, 1) + _int(4, 4096))
    host = _plane(
        3, "/host:CPU",
        [_line(1, "python", [(1, 0, 1000, b""), (2, 700, 100, bytes_stat),
                             (3, 10, 5, b"")])],
        ["bench.window", "fptc.drain.d2h", "PjitFunction(f)"], ["bytes"])
    hlo = _int(1, 1) + _msg(6, _hlo_proto())  # XStat: Hlo Proto bytes
    meta = _plane(4, "/host:metadata", [], [module], [progtrace.HLO_STAT],
                  {module: [hlo]})
    return _msg(1, device) + _msg(1, host) + _msg(1, meta)


def _write_xspace(log_dir):
    path = pathlib.Path(log_dir) / "plugins" / "profile" / "t0" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xspace())
    return str(path)


def test_reduction_of_a_trace_file(tmp_path):
    path = _write_xspace(tmp_path)
    t = tr.from_xplane(path)
    assert t.window == (0.0, 1000.0)
    assert progtrace.hlo_scopes(pathlib.Path(path).read_bytes()) == {
        "jit__decode_bucket_phases(7)": {
            "while.4": "fptc.decode.huffman", "fusion.62": "fptc.decode.huffman",
            "sort": "fptc.decode.compact", "fusion.2": "fptc.decode.compact",
            "fusion.1": "fptc.decode.idct"}}
    pt = progtrace.from_xplane(path, t)
    assert pt.program_spans == [("fptc.drain.d2h", 700.0, 100.0, 4096)]
    assert pt.scoped_ops == {0: [
        ("fptc.decode.huffman", 100.0, 300.0),
        ("fptc.decode.huffman", 150.0, 100.0),
        ("fptc.decode.compact", 400.0, 100.0),
        ("fptc.decode.compact", 500.0, 50.0),
        ("fptc.decode.idct", 550.0, 100.0)]}


def test_readers_find_the_run_trace_file(tmp_path, monkeypatch):
    from fptcbench import harness

    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    path = _write_xspace(tmp_path / "trace" / "x")
    r = Run(cell="x", chips=1, seed=1, seconds=1, device={}, peaks={})
    r.trace = tr.from_xplane(path)
    assert _read("huffman_share.drain", r) == pytest.approx(30.0)
    assert _read("idle_d2h_share.drain", r) == pytest.approx(10.0)
    assert r.program_trace is progtrace.for_run(r)  # read once per run
    assert _read("idle_stitch_share.drain", r) is None  # no such span
