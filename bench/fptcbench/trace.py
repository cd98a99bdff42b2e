"""Reduction of a profiler trace to the numbers the readers need.

The trace is first cut down to plain tuples (``Trace``), which a test can
also build from a small recorded fixture:

  * ``ops[device]``      device operations (the "XLA Ops" line of each
                         ``/device:TPU:<id>`` plane): (name, start, duration)
  * ``modules[device]``  jitted programs (the "XLA Modules" line)
  * ``spans``            the benchmark's host annotations (``bench.*``)
  * ``window``           the ``bench.window`` annotation: (start, end)

Times are nanoseconds on the trace's clock.  Busy time is the union of a
device's op intervals inside the window; a program's time is the summed
duration of its module events whose name holds the jitted function's name.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    spans: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    # -- serialisation (fixtures) -------------------------------------------
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        fix = lambda m: {int(k): [tuple(e) for e in v] for k, v in m.items()}
        return cls(tuple(d["window"]), fix(d["ops"]), fix(d["modules"]),
                   [tuple(e) for e in d["spans"]])

    # -- reductions -----------------------------------------------------------
    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        return union(self.ops.get(device, ()), *self.window)

    def busy_s(self, device: int) -> float:
        return sum(b - a for a, b in self.busy_intervals(device)) * 1e-9

    def program_s(self, pattern: str, devices: Sequence[int]) -> float:
        lo, hi = self.window
        tot = 0.0
        for d in devices:
            for name, s, dur in self.modules.get(d, ()):
                if pattern in name:
                    tot += max(0.0, min(s + dur, hi) - max(s, lo))
        return tot * 1e-9

    def top_ops(self, devices: Sequence[int], k: int = 10) -> List[List]:
        lo, hi = self.window
        acc: Dict[str, float] = {}
        for d in devices:
            for name, s, dur in self.ops.get(d, ()):
                t = max(0.0, min(s + dur, hi) - max(s, lo))
                if t > 0:
                    acc[_op_family(name)] = acc.get(_op_family(name), 0.0) + t
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9 / max(len(devices), 1)] for n, t in top]

    def idle_gaps(self, device: int, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of ``device`` in the window, each
        named by the innermost benchmark span open on the host at its
        middle ("none" when none was)."""
        lo, hi = self.window
        busy = self.busy_intervals(device)
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for a, b in gaps[:k]:
            mid = 0.5 * (a + b)
            inner = [(s, n) for n, s, dur in self.spans
                     if n != WINDOW_SPAN and s <= mid <= s + dur]
            name = max(inner)[1] if inner else "none"
            out.append([name, (b - a) * 1e-9])
        return out


def _op_family(name: str) -> str:
    """An op's name without its HLO text and numeric suffix
    (``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion``)."""
    return re.sub(r"[.:]\d+$", "", name.split(" = ")[0].lstrip("%"))


def union(events, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of ``events`` clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s + d > lo and s < hi)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def from_xplane(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` to a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dst = ops if line.name == OPS_LINE else modules
                dst.setdefault(int(m.group(1)), []).extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events)
            elif not m and plane.name.startswith("/host"):
                spans.extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} annotation in the trace")
    _, s, d = max(win, key=lambda e: e[2])
    return Trace((s, s + d), ops, modules, spans)


def latest_xplane(log_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None

