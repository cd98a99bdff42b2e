"""Shared interval arithmetic of the phase readers (the device ops of one
``fptc.decode.*`` scope) and the idle readers (the device's idle time
inside one ``fptc.*`` host span), from ``fptcbench.progtrace``."""
import sys

from fptcbench import progtrace
from fptcbench.trace import union

# the jitted program whose device time the phase scopes split
PROGRAM = "_decode_bucket"


def overlap(a, b):
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _memo(run, key, fn):
    """``fn()``, computed once per run and kept on it for the other readers."""
    memo = run.__dict__.setdefault("phase_memo", {})
    if key not in memo:
        memo[key] = fn()
    return memo[key]


def idle(run, device):
    """``device``'s idle intervals inside the window."""
    def gaps():
        lo, hi = run.trace.window
        out, t = [], lo
        for a, b in run.trace.busy_intervals(device):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if hi > t:
            out.append((t, hi))
        return out

    return _memo(run, ("idle", device), gaps)


def unscoped_share(run, pt, device):
    """Share of the program's busy device time in no ``fptc.decode`` scope."""
    lo, hi = run.trace.window
    prog = union([e for e in run.trace.modules.get(device, ())
                  if PROGRAM in e[0]], lo, hi)
    busy = overlap(run.trace.busy_intervals(device), prog)
    scoped = overlap(pt.scope_intervals(device, progtrace.SCOPES, lo, hi), prog)
    return 100.0 * (1.0 - scoped / busy) if busy > 0 else None


def scope_share(run, metric, scope):
    """Union of the scope's op intervals over the window, averaged over
    the cell's chips."""
    pt = progtrace.for_run(run)
    if pt is None or not pt.scoped_ops:
        return None
    lo, hi = run.trace.window
    ids = range(run.chips)
    t = sum(b - a for d in ids
            for a, b in pt.scope_intervals(d, (scope,), lo, hi))
    rest = _memo(run, "unscoped",
                 lambda: [unscoped_share(run, pt, d) for d in ids])
    print(f"[bench] {metric['name']}: {PROGRAM} device time in no scope "
          f"{rest}%", file=sys.stderr)
    return 100.0 * t / (run.chips * (hi - lo))


def idle_share(run, metric, span):
    """Device idle time inside the span's intervals over the window,
    averaged over the cell's chips."""
    pt = progtrace.for_run(run)
    if pt is None:
        return None
    lo, hi = run.trace.window
    spans = pt.span_intervals(span, lo, hi)
    if not spans:
        return None
    t = sum(overlap(idle(run, d), spans) for d in range(run.chips))
    return 100.0 * t / (run.chips * (hi - lo))


def span_rate(run, span):
    """GB/s of the spans' ``bytes`` stat over their summed duration, for
    the spans that lie inside the window (None without such spans)."""
    pt = progtrace.for_run(run)
    if pt is None:
        return None
    lo, hi = run.trace.window
    inside = [(d, b) for n, s, d, b in pt.program_spans
              if n == span and b is not None and s >= lo and s + d <= hi]
    dur = sum(d for d, _ in inside)
    return sum(b for _, b in inside) / dur if dur > 0 else None
