"""What one run records: host spans, counters, compile time, and (with
``--trace 1``) the profiler's trace.  Readers in ``bench/metrics`` take
their numbers from here."""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Run", "Spans", "CompileClock"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds and count of XLA backend compiles (or their loads from the
    persistent cache), from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def listen(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.count += 1

    def read(self) -> Tuple[float, int]:
        with self._lock:
            return self.seconds, self.count


class Spans:
    """Host spans of the benchmark's own code.  Each is kept in memory
    (name, start, end on ``perf_counter``) and, while tracing, also written
    into the profiler's trace as a ``TraceAnnotation``."""

    def __init__(self):
        self.items: List[Tuple[str, float, float]] = []
        self.tracing = False
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.items.append((name, t0, t1))

    def total(self, name: str, lo: float, hi: float) -> float:
        """Seconds spent in spans ``name`` inside [lo, hi]."""
        with self._lock:
            items = list(self.items)
        return sum(max(0.0, min(b, hi) - max(a, lo))
                   for n, a, b in items if n == name)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: str
    chips: int
    seed: int
    seconds: int
    device: Dict[str, Any]
    peaks: Dict[str, float]
    setup_s: float = 0.0
    setup_compile_s: float = 0.0
    window_t0: float = 0.0
    window_t1: float = 0.0
    window_compile_s: float = 0.0
    window_compiles: int = 0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    work: Dict[str, Tuple[float, float]] = dataclasses.field(default_factory=dict)
    spans: Optional[Spans] = None
    trace: Any = None  # fptcbench.trace.Trace with --trace 1

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def span_total(self, name: str) -> float:
        return self.spans.total(name, self.window_t0, self.window_t1)

    def add_work(self, program: str, flops: float, nbytes: float) -> None:
        f, b = self.work.get(program, (0.0, 0.0))
        self.work[program] = (f + flops, b + nbytes)
