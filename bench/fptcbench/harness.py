"""One run of one cell: device check, set-up, the measured window, the
check against the reference, and the result line."""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from . import spec
from .record import CompileClock, Run, Spans

__all__ = ["Ctx", "Verdict", "NoChipError", "run_cell", "main"]

OUT_DIR = spec.ROOT / "bench_out"


class NoChipError(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Verdict:
    correct: bool
    attempted: int
    failed: int
    checks: List[Tuple[str, float, float]]  # (name, value, limit)


@dataclasses.dataclass
class Ctx:
    """What a driver is given: the cell, the run's seed and window, where
    the engines run, the host spans, and the configuration."""

    cell: spec.Cell
    seed: int
    seconds: float
    devices: Any  # the engines' ``devices`` argument
    spans: Spans
    config: Dict[str, Any]  # the cell's configuration (tests may shrink it)


def device_check(chips: int, require_chip: bool) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChipError(f"the first device is {devs[0].platform!r} "
                          f"({devs[0].device_kind}); this benchmark runs on a "
                          "TPU only")
    if require_chip and len(devs) < chips:
        raise NoChipError(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend without memory stats
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run ``cell_name`` once; returns the result object.  ``overrides``
    replaces top-level keys of the configuration (tests run a cell small on
    the CPU with ``require_chip=False``)."""
    cell = spec.load_cell(cell_name)
    device = device_check(cell.chips, require_chip)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    from .peaks import peaks_for

    if require_chip:
        enable_compile_cache()
    clock = CompileClock()
    clock.listen()
    used = jax.devices()[: cell.chips]
    ctx = Ctx(
        cell=cell, seed=seed, seconds=seconds,
        devices=None if cell.chips == 1 else "auto", spans=Spans(),
        config=dict(cell.config, **(overrides or {})),
    )
    drv = spec.driver(cell.traffic["driver"])
    peaks = peaks_for(device["kind"]) if require_chip else {}
    run = Run(cell=cell.name, chips=cell.chips, seed=seed, seconds=int(seconds),
              device=device, peaks=peaks, spans=ctx.spans)

    state = drv.setup(ctx)
    c0, n0 = clock.read()
    run.setup_compile_s = c0

    trace_dir = OUT_DIR / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=_profile_options())
        ctx.spans.tracing = True
    run.setup_s = time.perf_counter() - t_start
    run.window_t0 = time.perf_counter()
    try:
        with ctx.spans.span("bench.window"):
            drv.window(ctx, state, run)
    finally:
        run.window_t1 = time.perf_counter()
        if trace:
            ctx.spans.tracing = False
            jax.profiler.stop_trace()
    c1, n1 = clock.read()
    run.window_compile_s, run.window_compiles = c1 - c0, n1 - n0
    memory_peak = _peak_bytes(used)

    if trace:
        from . import trace as tr

        path = tr.latest_xplane(str(trace_dir))
        run.trace = tr.from_xplane(path)

    verdict: Verdict = drv.check(ctx, state, run)
    del state

    metrics: Dict[str, Dict[str, Any]] = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = spec.reader(m["name"]).read(run, m)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=memory_peak)
    result: Dict[str, Any] = {
        "correct": bool(verdict.correct),
        "attempted": int(verdict.attempted),
        "failed": int(verdict.failed),
        "metrics": metrics,
        "device": dev,
    }
    ids = [d.id for d in used]
    if trace:
        t = run.trace
        dev["busy_s"] = sum(t.busy_s(i) for i in ids) / len(ids)
        dev["window_s"] = t.window_s
        result["breakdown"] = {
            "device_ops": t.top_ops(ids),
            "idle_gaps": t.idle_gaps(ids[0]),
        }
    setup_spans = {n[len("bench."):] + "_s": ctx.spans.total(n, 0.0, run.window_t0)
                   for n in ("bench.generate", "bench.ingest", "bench.warmup")}
    notes = {
        "setup_s": run.setup_s, "setup_compile_s": run.setup_compile_s,
        **setup_spans,
        "window_s": run.window_s, "window_compiles": run.window_compiles,
        "window_compile_s": run.window_compile_s, **run.counters,
    }
    print("[bench] " + json.dumps(notes, default=float), file=sys.stderr,
          flush=True)
    result["checks"] = {n: {"value": _num(v), "limit": l}
                        for n, v, l in verdict.checks}
    return result


def _num(v: float) -> float:
    """A compared number as JSON can hold it (an unreadable answer reads
    as 1e30, far past any limit)."""
    return 1e30 if not math.isfinite(v) else float(v)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace),
                          t_start=t_start if t_start is not None else time.perf_counter())
    except NoChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
