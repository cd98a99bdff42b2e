"""Megakernel block-size autotuner with a persisted on-disk tuning cache.

``BLOCK_WORDS``/``BLOCK_WINDOWS`` in the decode megakernel and the encode
kernel's rows-per-grid-step were hand-picked constants; this module sweeps
candidate blocks (interpret mode on CPU, real kernels on TPU) and records
the winner in a :class:`TuningCache`:

  * **keyed like the serving ``PlanCache``** — by (kind, backend,
    plan key, bucket shape), so a tuned entry is exactly as specific as
    the jit specialization it configures;
  * **persisted** — JSON under the ``FPTC_TUNING_CACHE`` directory (unset:
    in-memory only), written atomically (tmp + ``os.replace``), loaded
    lazily; corrupt files and stale/invalid entries are *rejected and
    re-tuned*, never trusted;
  * **thread-safe** — one ``RLock`` around the in-memory map and all file
    IO, mirroring the PlanCache discipline (the engines' staging worker
    may race the dispatch thread into a lookup).

``kernels/ops.py`` consults :func:`tuned_blocks` at trace time when the
caller didn't pin blocks explicitly; the serving engines pass the global
:func:`epoch` counter (bumped on every store) as a static jit argument, so
a newly-tuned entry *retraces* the affected bucket shapes instead of being
silently shadowed by an older specialization.  Block sizes change kernel
scheduling only — never bytes (pinned by the warm-vs-cold cache
byte-identity tests).
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence

__all__ = [
    "TuningCache",
    "default_cache",
    "set_default_cache",
    "epoch",
    "tuned_blocks",
    "tune",
    "decode_block_candidates",
    "encode_block_candidates",
    "tune_decode_bucket",
    "tune_encode_bucket",
]

ENV_DIR = "FPTC_TUNING_CACHE"
CACHE_VERSION = 1
_CACHE_FILE = "fptc_tuning.json"
# sanity range for any persisted block size: rejects corrupt/stale entries
_MAX_BLOCK = 1 << 20

Blocks = Dict[str, int]


def _entry_key(
    kind: str, backend: str, plan_key: Sequence, shape: Sequence[int]
) -> str:
    plan = ",".join(str(int(p)) for p in plan_key)
    shp = "x".join(str(int(s)) for s in shape)
    return f"{kind}|{backend}|plan({plan})|shape({shp})"


def _valid_blocks(blocks) -> bool:
    if not isinstance(blocks, dict) or not blocks:
        return False
    for k, v in blocks.items():
        if not isinstance(k, str):
            return False
        if not isinstance(v, int) or isinstance(v, bool):
            return False
        if not 1 <= v <= _MAX_BLOCK:
            return False
    return True


class TuningCache:
    """Thread-safe, optionally-persisted map: tuning key -> winning blocks.

    ``directory=None`` resolves ``FPTC_TUNING_CACHE``; when that is unset
    too the cache is memory-only (same API, nothing touches disk).
    """

    def __init__(self, directory: Optional[str] = None):
        if directory is None:
            directory = os.environ.get(ENV_DIR, "").strip() or None
        self.directory = directory
        self._lock = threading.RLock()
        self._entries: Dict[str, dict] = {}
        self._loaded = False
        self.hits = 0
        self.misses = 0

    # -- persistence --------------------------------------------------------
    @property
    def path(self) -> Optional[str]:
        if self.directory is None:
            return None
        return os.path.join(self.directory, _CACHE_FILE)

    def _load_locked(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        path = self.path
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path, "r", encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError):
            # corrupt file: start empty — winners re-tune and overwrite
            return
        if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
            return  # stale schema: reject wholesale, re-tune
        entries = data.get("entries")
        if not isinstance(entries, dict):
            return
        for key, entry in entries.items():
            if (
                isinstance(key, str)
                and isinstance(entry, dict)
                and _valid_blocks(entry.get("blocks"))
            ):
                self._entries[key] = entry
            # invalid entries are dropped here → lookup misses → re-tuned

    def _save_locked(self) -> None:
        path = self.path
        if path is None:
            return
        os.makedirs(self.directory, exist_ok=True)
        payload = {"version": CACHE_VERSION, "entries": self._entries}
        fd, tmp = tempfile.mkstemp(
            dir=self.directory, prefix=_CACHE_FILE, suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # atomic: readers see old or new, whole
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- the map ------------------------------------------------------------
    def lookup(
        self,
        kind: str,
        backend: str,
        plan_key: Sequence,
        shape: Sequence[int],
    ) -> Optional[Blocks]:
        key = _entry_key(kind, backend, plan_key, shape)
        with self._lock:
            self._load_locked()
            entry = self._entries.get(key)
            if entry is None or not _valid_blocks(entry.get("blocks")):
                if entry is not None:
                    del self._entries[key]  # invalid in-memory entry
                self.misses += 1
                return None
            self.hits += 1
            return dict(entry["blocks"])

    def store(
        self,
        kind: str,
        backend: str,
        plan_key: Sequence,
        shape: Sequence[int],
        blocks: Blocks,
        *,
        sample_s: Optional[float] = None,
    ) -> None:
        if not _valid_blocks(blocks):
            raise ValueError(f"refusing to store invalid blocks {blocks!r}")
        key = _entry_key(kind, backend, plan_key, shape)
        entry = {"blocks": dict(blocks)}
        if sample_s is not None:
            entry["sample_s"] = float(sample_s)
        with self._lock:
            self._load_locked()
            self._entries[key] = entry
            self._save_locked()
        _bump_epoch()

    def __len__(self) -> int:
        with self._lock:
            self._load_locked()
            return len(self._entries)


# ---------------------------------------------------------------------------
# The process-default cache + the epoch the engines key their jits on.
# ---------------------------------------------------------------------------
_STATE_LOCK = threading.Lock()
_DEFAULT: Optional[TuningCache] = None
_DEFAULT_DIR: Optional[str] = None
_PINNED = False  # set_default_cache() pins: env re-resolution must not undo
_EPOCH = 0


def _bump_epoch() -> None:
    global _EPOCH
    with _STATE_LOCK:
        _EPOCH += 1


def epoch() -> int:
    """Monotone counter bumped on every cache store / default-cache swap.

    The serving engines pass it as a static argument to their kernel-path
    bucket jits, so tuning results that land after a shape was first traced
    still take effect (the jit retraces and the trace-time
    :func:`tuned_blocks` consult sees the new entry) — without it, an older
    specialization would silently shadow the tuned blocks.
    """
    with _STATE_LOCK:
        return _EPOCH


def default_cache() -> TuningCache:
    """The process-wide cache (re-resolves ``FPTC_TUNING_CACHE`` when the
    env changes, so tests and the CI leg can repoint it; an explicit
    :func:`set_default_cache` pin wins over the env until reset)."""
    global _DEFAULT, _DEFAULT_DIR
    env_dir = os.environ.get(ENV_DIR, "").strip() or None
    with _STATE_LOCK:
        if _DEFAULT is None or (not _PINNED and _DEFAULT_DIR != env_dir):
            _DEFAULT = TuningCache(env_dir)
            _DEFAULT_DIR = env_dir
            global _EPOCH
            _EPOCH += 1
        return _DEFAULT


def set_default_cache(cache: Optional[TuningCache]) -> None:
    """Pin (or with ``None`` reset to env resolution) the process-default
    cache explicitly — the pin survives later ``FPTC_TUNING_CACHE``
    changes until reset."""
    global _DEFAULT, _DEFAULT_DIR, _PINNED
    with _STATE_LOCK:
        _DEFAULT = cache
        _DEFAULT_DIR = cache.directory if cache is not None else None
        _PINNED = cache is not None
        global _EPOCH
        _EPOCH += 1


def tuned_blocks(
    kind: str,
    plan_key: Sequence,
    shape: Sequence[int],
    *,
    backend: Optional[str] = None,
) -> Blocks:
    """The kernels' consult path: the winning blocks for this (backend,
    plan key, bucket shape), or ``{}`` when nothing is tuned (callers then
    keep their built-in defaults)."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    blocks = default_cache().lookup(kind, backend, plan_key, shape)
    return blocks or {}


# ---------------------------------------------------------------------------
# The sweep.
# ---------------------------------------------------------------------------
# Single-flight registry for in-progress tunes: concurrent tune() calls on
# the same (cache, key) coalesce onto one sweep instead of each running the
# candidates AND each store()-ing (every store bumps the epoch, and every
# epoch bump retraces the kernel-path bucket jits — N racing front-end
# submitters would turn one tune into N sweeps and N retrace storms).
_TUNE_LOCK = threading.Lock()
_TUNE_INFLIGHT: Dict[tuple, threading.Event] = {}
def decode_block_candidates(
    words: int, windows: int
) -> List[Blocks]:
    """Default decode sweep grid: block_words x block_windows, clipped to
    the bucket (oversized candidates would all alias the same clipped
    kernel) and deduplicated."""
    out: List[Blocks] = []
    seen = set()
    for bw in (1024, 2048, 4096):
        for bn in (128, 256, 512):
            cand = (
                min(bw, max(int(words), 1)),
                min(bn, max(int(windows), 1)),
            )
            if cand in seen:
                continue
            seen.add(cand)
            out.append({"block_words": cand[0], "block_windows": cand[1]})
    return out


def encode_block_candidates(lanes: int) -> List[Blocks]:
    """Default encode sweep grid: chunks packed per grid step (a lane
    block of 128-multiples, or the whole bucket when it is narrower)."""
    out: List[Blocks] = []
    seen = set()
    for lb in (128, 256, 512, 1024):
        b = min(lb, max(int(lanes), 1))
        if b in seen:
            continue
        seen.add(b)
        out.append({"block_lanes": b})
    return out


def tune(
    kind: str,
    plan_key: Sequence,
    shape: Sequence[int],
    runner: Callable[[Blocks], None],
    candidates: Iterable[Blocks],
    *,
    cache: Optional[TuningCache] = None,
    backend: Optional[str] = None,
    trials: int = 3,
    warmup: int = 1,
    force: bool = False,
    rank: Optional[Callable[[Blocks], float]] = None,
    top_k: Optional[int] = None,
) -> Blocks:
    """Sweep ``candidates``, record the winner, return its blocks.

    ``runner(blocks)`` must execute ONE dispatch with the candidate blocks
    and block until the device finishes (compile cost is excluded by the
    ``warmup`` calls).  The cache is consulted first: a valid hit returns
    immediately *without running anything* (``force=True`` re-tunes).
    ``rank`` (e.g. a cost-model prediction) optionally orders candidates
    and ``top_k`` prunes the sweep to the model's best guesses.
    """
    if cache is None:
        cache = default_cache()
    if backend is None:
        import jax

        backend = jax.default_backend()
    flight_key = (id(cache), _entry_key(kind, backend, plan_key, shape))
    while True:
        if not force:
            hit = cache.lookup(kind, backend, plan_key, shape)
            if hit is not None:
                return hit
        with _TUNE_LOCK:
            done = _TUNE_INFLIGHT.get(flight_key)
            if done is None:
                _TUNE_INFLIGHT[flight_key] = done = threading.Event()
                break  # we lead this key's sweep
        # same key already tuning: wait, then take its fresh entry — even
        # under force (the entry postdates our call, so it IS a re-tune)
        done.wait()
        hit = cache.lookup(kind, backend, plan_key, shape)
        if hit is not None:
            return hit
        # the leader failed; loop and lead the sweep ourselves
    try:
        return _tune_locked(
            kind, plan_key, shape, runner, candidates, cache=cache,
            backend=backend, trials=trials, warmup=warmup, rank=rank,
            top_k=top_k,
        )
    finally:
        with _TUNE_LOCK:
            _TUNE_INFLIGHT.pop(flight_key, None)
        done.set()


def _tune_locked(
    kind: str,
    plan_key: Sequence,
    shape: Sequence[int],
    runner: Callable[[Blocks], None],
    candidates: Iterable[Blocks],
    *,
    cache: TuningCache,
    backend: str,
    trials: int,
    warmup: int,
    rank: Optional[Callable[[Blocks], float]],
    top_k: Optional[int],
) -> Blocks:
    """The sweep body; the caller holds this key's single-flight lease."""
    cands = list(candidates)
    if not cands:
        raise ValueError("tune() needs at least one candidate")
    if rank is not None:
        cands.sort(key=rank)
        if top_k is not None:
            cands = cands[: max(int(top_k), 1)]
    best: Optional[Blocks] = None
    best_t = float("inf")
    for blocks in cands:
        for _ in range(max(warmup, 0)):
            runner(blocks)
        times = []
        for _ in range(max(trials, 1)):
            t0 = time.perf_counter()
            runner(blocks)
            times.append(time.perf_counter() - t0)
        t = sorted(times)[len(times) // 2]
        if t < best_t:
            best, best_t = blocks, t
    assert best is not None
    cache.store(
        kind, backend, plan_key, shape, best, sample_s=best_t
    )
    return dict(best)


# ---------------------------------------------------------------------------
# Concrete sweeps over the fused kernels (the CLI / CI warm path).
# ---------------------------------------------------------------------------
def _synthetic_stream(tables, num_words: int, num_windows: int):
    """Representative packed words for a decode sweep: encode a random
    signal under ``tables`` (so symbol statistics match the codebook),
    then clip/pad the word stream to the requested bucket shape (SymLen
    words decode independently, so truncation stays well-formed; padding
    words carry symlen 0 and emit nothing)."""
    import numpy as np

    from repro.core import codec

    cfg = tables.config
    rng = np.random.default_rng(7)
    signal = rng.standard_normal(num_windows * cfg.n).astype(np.float32)
    container = codec.encode(signal, tables)
    hi, lo = container.words_u32()
    w = min(container.num_words, num_words)
    out_hi = np.zeros(num_words, np.uint32)
    out_lo = np.zeros(num_words, np.uint32)
    out_sl = np.zeros(num_words, np.int32)
    out_hi[:w] = hi[:w]
    out_lo[:w] = lo[:w]
    out_sl[:w] = container.symlen[:w]
    return out_hi, out_lo, out_sl, int(container.max_symlen)


def tune_decode_bucket(
    tables,
    *,
    num_words: int,
    num_windows: int,
    cache: Optional[TuningCache] = None,
    cost_model=None,
    trials: int = 3,
    force: bool = False,
    top_k: Optional[int] = None,
) -> Blocks:
    """Sweep the decode megakernel's (block_words, block_windows) for one
    (plan key, bucket shape); interpret mode on CPU, real on TPU."""
    import jax.numpy as jnp

    from repro.core.quantize import quant_grid, recon_scale
    from repro.kernels import ops as kops
    from repro.serving.engine import symlen_bucket

    cfg = tables.config
    hi, lo, sl, max_sl = _synthetic_stream(tables, num_words, num_windows)
    dev_tables = tables.device_tables()
    lut, _ = quant_grid(tables.quant)
    rscale = recon_scale(tables.quant)
    hi, lo, sl = jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(sl)
    ms = symlen_bucket(max_sl)
    # the EXACT key ops.decode_bucket_fused consults at trace time — block
    # choice depends on shapes, not domain identity
    plan_key = (cfg.n, cfg.e, cfg.l_max, ms)

    def runner(blocks: Blocks) -> None:
        out = kops.decode_bucket_fused(
            hi, lo, sl, dev_tables, lut, rscale,
            l_max=cfg.l_max, max_symlen=ms, num_windows=num_windows,
            n=cfg.n, e=cfg.e,
            block_words=blocks["block_words"],
            block_windows=blocks["block_windows"],
        )
        out.block_until_ready()

    rank = None
    if cost_model is not None:
        rank = lambda b: cost_model.decode_bucket_cost(  # noqa: E731
            num_words, num_windows, e=cfg.e, n=cfg.n, max_symlen=ms,
            block_words=b["block_words"], block_windows=b["block_windows"],
        )
    return tune(
        "decode", plan_key, (num_words, num_windows),
        runner, decode_block_candidates(num_words, num_windows),
        cache=cache, trials=trials, force=force, rank=rank, top_k=top_k,
    )


def tune_encode_bucket(
    tables,
    *,
    rows: int,
    num_windows: int,
    chunk_size: Optional[int] = None,
    cache: Optional[TuningCache] = None,
    cost_model=None,
    trials: int = 3,
    force: bool = False,
    top_k: Optional[int] = None,
) -> Blocks:
    """Sweep the encode pack kernel's chunks-per-grid-step for one
    (plan key, bucket shape)."""
    import numpy as np

    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.serving.batch_encode import _encode_symbols

    cfg = tables.config
    width = num_windows * cfg.n
    sp = num_windows * cfg.e
    chunk = sp if chunk_size is None else min(int(chunk_size), sp)
    # the EXACT key ops.encode_bucket_fused consults at trace time
    plan_key = (cfg.l_max, chunk)
    rng = np.random.default_rng(11)
    signals = jnp.asarray(
        rng.standard_normal((rows, width)).astype(np.float32)
    )
    counts = jnp.full((rows,), sp, dtype=jnp.int32)
    dev_tables = tables.device_tables()
    syms, valid, _, _ = _encode_symbols(
        signals, counts, dev_tables, n=cfg.n, e=cfg.e, check_gaps=False,
    )
    lanes = rows * -(-sp // chunk)

    def runner(blocks: Blocks) -> None:
        out = kops.encode_bucket_fused(
            syms, valid, dev_tables, chunk_size=chunk, l_max=cfg.l_max,
            block_lanes=blocks["block_lanes"],
        )
        out[3].block_until_ready()

    rank = None
    if cost_model is not None:
        rank = lambda b: cost_model.encode_bucket_cost(  # noqa: E731
            rows, num_windows, e=cfg.e, n=cfg.n, chunk_size=chunk,
            block_lanes=b["block_lanes"],
        )
    return tune(
        "encode", plan_key, (rows, sp),
        runner, encode_block_candidates(lanes),
        cache=cache, trials=trials, force=force, rank=rank, top_k=top_k,
    )


# ---------------------------------------------------------------------------
# CLI: pre-populate the cache for a grid of serving bucket shapes.
# ---------------------------------------------------------------------------
def _main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="Warm the FPTC kernel tuning cache "
        f"(${ENV_DIR} or --cache-dir) for common serving bucket shapes."
    )
    parser.add_argument(
        "--cache-dir", default=None,
        help=f"cache directory (default: ${ENV_DIR})",
    )
    parser.add_argument(
        "--datasets", nargs="*", default=["load_power", "temperature"],
        help="calibration datasets to tune plans for",
    )
    parser.add_argument(
        "--smoke", action="store_true",
        help="small shapes + fewer trials (CI-sized)",
    )
    parser.add_argument("--force", action="store_true", help="re-tune hits")
    args = parser.parse_args(argv)

    from repro.core import DOMAIN_DEFAULTS, calibrate
    from repro.data import make_signal
    from repro.data.signals import domain_of
    from repro.tuning.cost_model import default_cost_model

    import numpy as np

    cache = TuningCache(args.cache_dir) if args.cache_dir else default_cache()
    cm = default_cost_model()
    trials = 1 if args.smoke else 3
    shapes = (
        [(4096, 512), (16384, 2048)]
        if args.smoke
        else [(4096, 512), (16384, 2048), (65536, 8192)]
    )
    enc_shapes = [(8, 32), (16, 128)] if args.smoke else [
        (8, 32), (16, 128), (32, 512)
    ]
    for dataset in args.datasets:
        dom = domain_of(dataset)
        calib = np.concatenate(
            [make_signal(dataset, 65536, seed=90 + i) for i in range(2)]
        )
        tables = calibrate(calib, DOMAIN_DEFAULTS[dom])
        for words, windows in shapes:
            blocks = tune_decode_bucket(
                tables, num_words=words, num_windows=windows,
                cache=cache, cost_model=cm, trials=trials,
                force=args.force, top_k=4 if args.smoke else None,
            )
            print(f"decode {dataset} ({words}w,{windows}win): {blocks}")
        for rows, windows in enc_shapes:
            blocks = tune_encode_bucket(
                tables, rows=rows, num_windows=windows,
                cache=cache, cost_model=cm, trials=trials,
                force=args.force,
            )
            print(f"encode {dataset} ({rows}r,{windows}win): {blocks}")
    where = cache.path or "(memory only)"
    print(f"tuning cache: {len(cache)} entries at {where}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(_main())
