"""The program's own spans and scopes in a profiler trace, on top of the
reduction in ``fptcbench.trace`` (which it leaves as it is):

  * ``program_spans``       the program's host spans (events named
                            ``fptc.*``, opened by ``repro.serving.engine.span``):
                            (name, start, duration, ``bytes`` stat or None)
  * ``scoped_ops[device]``  the device op events whose HLO ``op_name``
                            metadata holds an ``fptc.decode.*`` named scope:
                            (scope, start, duration)

Times are nanoseconds on the trace's clock, the clock of ``Trace``.

On a TPU v5e an op event carries no ``op_name``: its stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``
alone.  The trace does hold the HLO of every program it ran, one
``HloProto`` per program in the ``/host:metadata`` plane (stat ``Hlo Proto``
of an event metadata named like the program's module events).  So an op's
scope is found by a join: the op event lies inside one module event of its
device, its name starts with its HLO instruction's name
(``%fusion.62 = ...``), and that instruction's ``metadata.op_name`` in the
module's ``HloProto`` holds the scope path
(``jit(f)/fptc.decode.huffman/while/body/...``).  The python profiler API
does not reach event metadata, so the few fields needed are read straight
from the protobuf wire format (field numbers of ``tsl/profiler/protobuf/
xplane.proto`` and ``xla/service/hlo.proto``).
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re
from typing import Dict, Iterator, List, Optional, Tuple

from .trace import _DEVICE, Event, Trace, latest_xplane, union

SPAN_PREFIX = "fptc."
SCOPES = ("fptc.decode.huffman", "fptc.decode.compact", "fptc.decode.idct")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
_SCOPE = re.compile(r"(?:^|/)(fptc\.decode\.[A-Za-z0-9_]+)(?:/|$)")

Span = Tuple[str, float, float, Optional[int]]  # name, start, dur, bytes
Interval = Tuple[float, float]


@dataclasses.dataclass
class ProgramTrace:
    program_spans: List[Span] = dataclasses.field(default_factory=list)
    scoped_ops: Dict[int, List[Event]] = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "ProgramTrace":
        d = json.loads(text)
        return cls([tuple(e) for e in d["program_spans"]],
                   {int(k): [tuple(e) for e in v]
                    for k, v in d["scoped_ops"].items()})

    def span_intervals(self, name: str, lo: float, hi: float) -> List[Interval]:
        """Merged intervals of the spans called ``name``, clipped to [lo, hi]."""
        return union([(n, s, d) for n, s, d, _ in self.program_spans
                      if n == name], lo, hi)

    def scope_intervals(self, device: int, scopes, lo: float,
                        hi: float) -> List[Interval]:
        """Merged intervals of ``device``'s ops in any of ``scopes``."""
        return union([e for e in self.scoped_ops.get(device, ())
                      if e[0] in scopes], lo, hi)


# -- protobuf wire format ------------------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message in ``buf[lo:hi]``: an int for a
    varint, (start, end) for a length-delimited field, None otherwise."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            yield field, None
        else:
            raise ValueError(f"protobuf wire type {wire} not expected")


def _sub(buf, span, field: int) -> Iterator[Tuple[int, int]]:
    """The length-delimited occurrences of ``field`` in the message at ``span``."""
    for f, v in _fields(buf, *span):
        if f == field and isinstance(v, tuple):
            yield v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def hlo_scopes(raw: bytes) -> Dict[str, Dict[str, str]]:
    """{module name: {HLO instruction name: fptc.decode scope}} of the
    programs whose ``HloProto`` the trace (an ``XSpace``) holds."""
    buf = memoryview(raw)
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(buf, (0, len(buf)), 1):  # XSpace.planes
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for f, v in fields if f == 2), "")
        if name != METADATA_PLANE:
            continue
        stat_names = {}
        for f, entry in fields:  # XPlane.stat_metadata: map<int64, XStatMetadata>
            if f == 5:
                for md in _sub(buf, entry, 2):
                    kv = dict(_fields(buf, *md))
                    if isinstance(kv.get(2), tuple):
                        stat_names[kv.get(1, 0)] = _text(buf, kv[2])
        for f, entry in fields:  # XPlane.event_metadata: map<int64, XEventMetadata>
            if f != 4:
                continue
            for md in _sub(buf, entry, 2):
                module, protos = "", []
                for g, v in _fields(buf, *md):
                    if g == 2 and isinstance(v, tuple):  # XEventMetadata.name
                        module = _text(buf, v)
                    elif g == 5 and isinstance(v, tuple):  # XEventMetadata.stats
                        st = dict(_fields(buf, *v))
                        if (stat_names.get(st.get(1)) == HLO_STAT
                                and isinstance(st.get(6), tuple)):
                            protos.append(st[6])  # XStat.bytes_value
                if module and protos:
                    out[module] = _instruction_scopes(buf, protos[0])
    return out


def _instruction_scopes(buf, proto) -> Dict[str, str]:
    """HloProto.hlo_module -> computations -> instructions: name -> scope."""
    scopes: Dict[str, str] = {}
    for module in _sub(buf, proto, 1):
        for comp in _sub(buf, module, 3):
            for inst in _sub(buf, comp, 2):
                name = op_name = None
                for f, v in _fields(buf, *inst):
                    if f == 1 and isinstance(v, tuple):
                        name = _text(buf, v)
                    elif f == 7 and isinstance(v, tuple):  # OpMetadata
                        for meta in _sub(buf, v, 2):
                            op_name = _text(buf, meta)
                m = _SCOPE.search(op_name) if (name and op_name) else None
                if m:
                    scopes[name] = m.group(1)
    return scopes


def instruction(op_event_name: str) -> str:
    """``%fusion.62 = s32[...] fusion(...)`` -> ``fusion.62``."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


def scope_ops(ops: List[Event], modules: List[Event],
              scopes: Dict[str, Dict[str, str]]) -> List[Event]:
    """The events of ``ops`` in an ``fptc.decode`` scope, as (scope, start,
    duration): each op is read in the module event that holds its start."""
    mods = sorted((s, s + d, n) for n, s, d in modules if n in scopes)
    starts = [m[0] for m in mods]
    out: List[Event] = []
    for name, s, d in ops:
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s < mods[k][1]:
            sc = scopes[mods[k][2]].get(instruction(name))
            if sc:
                out.append((sc, s, d))
    return out


def from_xplane(path: str, trace: Trace) -> ProgramTrace:
    """Read the program's spans and scoped ops from the ``.xplane.pb`` that
    ``trace`` was reduced from; op and module events come from ``trace``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    scopes = hlo_scopes(raw)
    spans: List[Span] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if _DEVICE.match(plane.name) or not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    nbytes = dict(ev.stats).get("bytes")
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.duration_ns),
                                  None if nbytes is None else int(nbytes)))
    scoped = {d: scope_ops(ops, trace.modules.get(d, []), scopes)
              for d, ops in trace.ops.items()} if scopes else {}
    return ProgramTrace(spans, {d: v for d, v in scoped.items() if v})


def for_run(run) -> Optional[ProgramTrace]:
    """The run's program trace, read once from its trace directory and
    kept on the run for the other readers (None without ``--trace 1``)."""
    if run.trace is None:
        return None
    pt = getattr(run, "program_trace", None)
    if pt is None:
        from .harness import OUT_DIR

        path = latest_xplane(str(OUT_DIR / "trace" / run.cell))
        pt = from_xplane(path, run.trace) if path else ProgramTrace()
        run.program_trace = pt
    return pt
