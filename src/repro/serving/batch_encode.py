"""Batched bucketed encode engine: one fused dispatch per shape bucket.

PR 1 made decode archive-scale; this is the encode-side mirror, built for
server-side ingest/transcoding and re-encode benchmarks (the paper's
*embedded* encoder stays ``core.codec.encode`` — sequential by design).
A per-signal ``encode_device`` loop pays the same three taxes the decode
engine removed, plus one of its own:

  1. **serial packing** — ``symlen.pack_symlen_scan`` is one ``lax.scan``
     step per symbol, a length-S dependency chain that no amount of batching
     hides;
  2. **recompilation** — per-signal jit retraces for every distinct length;
  3. **table re-upload + host sync** — tables travel per call and
     ``int(num_words)`` blocks on every container.

This module removes all four:

  * **Chunk-parallel packing.**  ``symlen.pack_symlen_chunked_parts`` packs
    B fixed-size chunks concurrently (vmap of scan-lite chunk packs — the
    scan carries only the O(1) bit-offset recurrence; words materialize as
    cumsum differences at searchsorted segment boundaries, scatter-free).
    The SymLen format makes the chunked output decoder-compatible bit for
    bit (each word is independently decodable), at < 1 padding word per
    chunk of stream growth.
  * **Shape bucketing.**  Signals are grouped by (domain, config) and padded
    into power-of-two window/batch buckets, so jit specializations are
    O(log sizes).  Per-signal symbol counts ride a device array into the
    packer's validity mask — never trace constants.
  * **Persistent encode plans.**  Device tables upload once per
    (domain, config, shard device) into an LRU :class:`EncodePlan` cache.
  * **Device-resident results.**  Encoded streams stay on device inside an
    :class:`EncodedBatch` until an explicit ``.to_host()`` drain — one sync
    per bucket, where the zero-length-codeword flag is also checked (the
    device-side arm of the ``pack_symlen_np`` histogram-gap guard).

Scheduling, pipelining and sharding ride the shared
:mod:`repro.serving.engine` layer: bucket k+1's host stacking + upload
overlap bucket k's fused DCT+quant+pack, and with several devices each
bucket's batch axis splits into per-device shards (rows pack
independently, so per-signal bytes never depend on which shard packed
them).  Device-resident staging uses the :class:`~repro.serving.engine.
GatherStage` contract — the gather then happens *inside* the bucket's
fused dispatch (one jit per bucket, optionally donating the source
buffer on its last use).

``core.codec.encode_device`` is a batch-of-one wrapper over this engine in
*exact* mode (``chunk_size=None`` — one chunk per signal), which keeps its
output bit-identical to the host encoder.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import (
    Any,
    Dict,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dct, symlen
from repro.core.calibration import DeviceTables, DomainTables
from repro.core.container import Container
from repro.core.quantize import predict_levels, quantize
from repro.serving._plans import (
    TRIVIAL_CODING,
    PlanCache,
    normalize_plan_key,
)
from repro.serving.engine import (
    Bucket,
    BucketScheduler,
    DevicesArg,
    GatherStage,
    PipelineExecutor,
    SubmitBuffer,
    default_use_kernels,
    fetch_to_host_stitched,
    putter,
)
from repro.tuning import autotune as _autotune
from repro.tuning.cost_model import CostModel, default_cost_model
from repro.tuning.policy import PolicyArg

__all__ = [
    "BatchEncoder",
    "EncodedBatch",
    "EncodedBucketParts",
    "EncodePlan",
    "default_encoder",
    "DEFAULT_CHUNK_SIZE",
]

TablesArg = Union[DomainTables, Mapping[int, DomainTables]]

# Symbols per packing chunk.  Words per chunk ~= chunk * avg_bits / 64, so at
# ~4 bits/symbol a 1024-symbol chunk spans ~64 words and the <1-word-per-chunk
# padding bound costs < ~1.6% stream growth, while the packing scan shrinks
# from length S to length 1024 with S/1024 parallel lanes per signal.
DEFAULT_CHUNK_SIZE = 1024


# ---------------------------------------------------------------------------
# Encode plans: per-(domain, config, shard) device state, uploaded once.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EncodePlan:
    """Device-resident encode state for one (domain, config) on one shard.

    Everything here is batch-size independent: one plan serves every bucket
    shape on its device.  ``has_gaps`` records (host-side, at plan build)
    whether the Huffman book has zero-length entries — only then does the
    fused encode pay for the device-side unencodable-symbol check.
    """

    tables: DeviceTables
    n: int
    e: int
    l_max: int
    domain_id: int
    has_gaps: bool
    device: object
    source: DomainTables  # host tables (kept so cache keys stay alive)
    # container-v3 coding triple (pred_id, predict_bands, zero_planes);
    # TRIVIAL_CODING selects the classic v2 stream byte-for-byte
    coding: Tuple[int, int, bool] = TRIVIAL_CODING


def _build_encode_plan(tables: DomainTables, key, device) -> EncodePlan:
    domain_id, n, e, l_max, coding = normalize_plan_key(key)
    dev_tables = tables.device_tables()
    if device is not None:
        dev_tables = jax.device_put(dev_tables, device)
    return EncodePlan(
        tables=dev_tables,
        n=n,
        e=e,
        l_max=l_max,
        domain_id=domain_id,
        has_gaps=bool(np.any(np.asarray(tables.book.lengths) == 0)),
        device=device,
        source=tables,
        coding=coding,
    )


# ---------------------------------------------------------------------------
# The fused bucket encode — ONE jit specialization per bucket shape.
# ---------------------------------------------------------------------------
def _encode_symbols(
    signals: jnp.ndarray,  # f32[K, Wp * n] (zero-padded signals)
    counts: jnp.ndarray,  # int32[K] true symbol count per signal
    tables: DeviceTables,
    *,
    n: int,
    e: int,
    check_gaps: bool,
    coding: Tuple[int, int, bool] = TRIVIAL_CODING,
):
    """The front half of a bucket encode: DCT + quantize (+ the v3 coding
    stage) down to the symbols the packer consumes.

    Returns ``(syms int32[K, Sp], valid bool[K, Sp], bad bool[K], extras)``
    — ``valid`` masks the slots that pack (true windows, minus v3
    zero-plane cells), ``bad`` the per-row unencodable-symbol flags (const
    False unless the book has histogram gaps), and ``extras`` the v3
    outputs ``(ncoded, zrow, zcol)`` (empty for the v2 stream).  Both pack
    arms — the XLA chunk packer and the Pallas pack kernel — consume
    exactly these arrays, which is what makes them byte-identical.
    """
    windows = dct.window_signal(signals, n)  # [K, Wp, n]
    coeffs = dct.forward_dct(windows, e)  # [K, Wp, e]
    grid = quantize(coeffs, tables.quant)  # uint8[K, Wp, e]
    k = signals.shape[0]
    if coding == TRIVIAL_CODING:
        syms = grid.reshape(k, -1).astype(jnp.int32)  # [K, Sp]
        valid = (
            jnp.arange(syms.shape[1], dtype=jnp.int32)[None, :]
            < counts[:, None]
        )
        extras = ()
    else:
        pred_id, bands, zplanes = coding
        grid = predict_levels(grid, pred_id, bands)  # uint8[K, Wp, e]
        syms = grid.reshape(k, -1).astype(jnp.int32)  # [K, Sp]
        # true-window mask: batch/window padding quantizes to 128 but its
        # *residuals* need not be 128, so every v3 mask is gated on it
        win_valid = (
            jnp.arange(grid.shape[1], dtype=jnp.int32)[None, :]
            < (counts // e)[:, None]
        )  # bool[K, Wp]
        if zplanes:
            is_zero = grid == jnp.uint8(128)
            # zrow over all rows (padding rows are garbage but the drain
            # slices mask[:num_windows]); zcol over VALID rows only,
            # matching the host encoder's grid which has num_windows rows
            zrow = jnp.all(is_zero, axis=2)  # bool[K, Wp]
            zcol = jnp.all(is_zero | ~win_valid[:, :, None], axis=1)
            valid = (win_valid & ~zrow)[:, :, None] & ~zcol[:, None, :]
            valid = valid.reshape(k, -1)
            extras = (jnp.sum(valid, axis=1, dtype=jnp.int32), zrow, zcol)
        else:
            valid = jnp.broadcast_to(win_valid[:, :, None], grid.shape)
            valid = valid.reshape(k, -1)
            extras = (counts, None, None)
    if check_gaps:
        bad = jnp.any((tables.lengths[syms] == 0) & valid, axis=1)
    else:
        bad = jnp.zeros((k,), jnp.bool_)
    return syms, valid, bad, extras


def _encode_bucket_math(
    signals: jnp.ndarray,  # f32[K, Wp * n] (zero-padded signals)
    counts: jnp.ndarray,  # int32[K] true symbol count per signal
    tables: DeviceTables,
    *,
    n: int,
    e: int,
    chunk_size: int,
    check_gaps: bool,
    coding: Tuple[int, int, bool] = TRIVIAL_CODING,
):
    """DCT + quantize + chunk-parallel pack for one shape bucket.

    Statics are *bucket shape only*; per-signal true lengths ride in
    ``counts`` and become the packer's validity mask, so zero-padded windows
    contribute no symbols to any stream.  Returns the per-signal *chunk
    parts* (hi/lo/symlen ``[K, B, chunk_size]`` + words-per-chunk
    ``[K, B]``) — the drain concatenates chunk runs on the host, which is
    cheaper than a device-side stitch and byte-identical — plus the
    PER-ROW unencodable-symbol flags ``bool[K]`` (const False unless the
    book has histogram gaps; padding rows have no valid symbols and stay
    False).  Per-row rather than batch-wide is what lets the serving
    quarantine demote a histogram gap from batch-fatal to a per-signal
    outcome at drain.

    A non-trivial ``coding`` (container v3) inserts the lossless pre-entropy
    stage between quantize and pack: windowed prediction re-codes the low
    bands as mod-256 residuals (``quantize.predict_levels`` — row-local, so
    it vmaps over the batch with no cross-signal state), and zero-plane
    suppression masks all-128 window rows / coefficient columns out of the
    packer's validity mask (the masked chunk packer emits nothing for them,
    so the stream equals a greedy pack of the compacted symbols).  The v3
    return adds per-signal coded-symbol counts and — under zero planes —
    the row/column bitmaps: ``(hi, lo, sl, wpc, bad, ncoded, zrow, zcol)``.
    """
    syms, valid, bad, extras = _encode_symbols(
        signals, counts, tables, n=n, e=e, check_gaps=check_gaps,
        coding=coding,
    )
    hi, lo, sl, wpc = jax.vmap(
        lambda s, v: symlen.pack_symlen_chunked_parts(
            s, tables.codes, tables.lengths, chunk_size=chunk_size, valid=v,
        )
    )(syms, valid)
    return (hi, lo, sl, wpc, bad) + extras


_encode_bucket = functools.partial(
    jax.jit,
    static_argnames=("n", "e", "chunk_size", "check_gaps", "coding"),
)(_encode_bucket_math)


def _gather_rows_math(
    flat: jnp.ndarray,  # f32[T + width] (flattened decoded windows)
    starts: jnp.ndarray,  # int32[K] first-sample flat offset per row
    lens: jnp.ndarray,  # int32[K] true sample count per row
    width: int,
) -> jnp.ndarray:
    """Stage one encode bucket's signal matrix ``f32[K, width]`` on device.

    Row ``r`` gathers samples ``[starts[r], starts[r] + lens[r])`` of the
    flattened window tensors and is exact-zero beyond ``lens[r]`` — the
    same layout ``BatchEncoder.encode`` stages host-side (a decoded
    signal's own window padding is *re-decoded* data, not zeros, so the
    mask is what keeps device staging bit-identical to the host path).

    ``flat`` must already carry >= ``width`` trailing zeros past the last
    real start (the transcode pipeline pads ONCE by the widest bucket) so
    every slice stays in bounds — dynamic_slice clamps out-of-range starts,
    which would silently shift a tail row's window otherwise.  Every row is
    one contiguous sample run, so the cheap lowering is a batched
    dynamic_slice (row-wise block copy) + tail mask — NOT a per-element
    gather, which costs ~2x the fused encode itself on CPU.
    """
    pos = jnp.arange(width, dtype=jnp.int32)

    def row(start, length):
        x = jax.lax.dynamic_slice(flat, (start,), (width,))
        return jnp.where(pos < length, x, jnp.zeros((), flat.dtype))

    return jax.vmap(row)(starts, lens)


def _encode_bucket_gather_math(
    flat: jnp.ndarray,
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    counts: jnp.ndarray,
    tables: DeviceTables,
    *,
    width: int,
    n: int,
    e: int,
    chunk_size: int,
    check_gaps: bool,
    coding: Tuple[int, int, bool] = TRIVIAL_CODING,
):
    """Device staging fused INTO the bucket encode: gather + DCT + quantize
    + pack in one jit per bucket (the former separate ``_gather_rows``
    dispatch is gone — its output never materializes in HBM between two
    launches)."""
    x = _gather_rows_math(flat, starts, lens, width)
    return _encode_bucket_math(
        x, counts, tables, n=n, e=e, chunk_size=chunk_size,
        check_gaps=check_gaps, coding=coding,
    )


_GATHER_STATICS = ("width", "n", "e", "chunk_size", "check_gaps", "coding")
_encode_bucket_gather = functools.partial(
    jax.jit, static_argnames=_GATHER_STATICS
)(_encode_bucket_gather_math)
# the last bucket to read a GatherStage's flat tensor may donate it, letting
# XLA reuse the decoded-window buffer for the pack outputs (no-op on CPU,
# where donation is unsupported — callers gate on the device platform)
_encode_bucket_gather_donate = functools.partial(
    jax.jit, static_argnames=_GATHER_STATICS, donate_argnums=(0,)
)(_encode_bucket_gather_math)


def _donation_supported(device) -> bool:
    platform = device.platform if device is not None else jax.default_backend()
    return platform in ("gpu", "tpu")


# ---------------------------------------------------------------------------
# The kernel-path twins: the same DCT + quantize front half, then the
# Pallas pack kernel instead of the XLA chunk packer — byte-identical chunk
# parts (pinned by the golden/conformance suites), one pallas_call per
# bucket.
# ---------------------------------------------------------------------------
def _encode_bucket_kernels_math(
    signals, counts, tables, *, n, e, l_max, chunk_size, check_gaps,
    coding=TRIVIAL_CODING, tuning_epoch=0,
):
    # tuning_epoch is a pure retrace key (see batch_decode._decode_bucket):
    # the kernel resolves its lanes-per-step block from the tuning cache at
    # trace time, so a cache store must invalidate old specializations
    del tuning_epoch
    from repro.kernels import ops as kops

    syms, valid, bad, extras = _encode_symbols(
        signals, counts, tables, n=n, e=e, check_gaps=check_gaps,
        coding=coding,
    )
    hi, lo, sl, wpc = kops.encode_bucket_fused(
        syms, valid, tables, chunk_size=chunk_size, l_max=l_max,
    )
    return (hi, lo, sl, wpc, bad) + extras


_KERNEL_STATICS = (
    "n", "e", "l_max", "chunk_size", "check_gaps", "coding", "tuning_epoch"
)
_encode_bucket_kernels = functools.partial(
    jax.jit, static_argnames=_KERNEL_STATICS
)(_encode_bucket_kernels_math)


def _encode_bucket_gather_kernels_math(
    flat, starts, lens, counts, tables,
    *, width, n, e, l_max, chunk_size, check_gaps, coding=TRIVIAL_CODING,
    tuning_epoch=0,
):
    """GatherStage staging for the kernel path: the row gather stays an XLA
    ``dynamic_slice`` batch fused into the same jit as the pallas_call (the
    gather feeds straight into the kernel's operand; no HBM round trip of a
    separately-dispatched signal matrix)."""
    x = _gather_rows_math(flat, starts, lens, width)
    return _encode_bucket_kernels_math(
        x, counts, tables,
        n=n, e=e, l_max=l_max, chunk_size=chunk_size,
        check_gaps=check_gaps, coding=coding, tuning_epoch=tuning_epoch,
    )


_GATHER_KERNEL_STATICS = _KERNEL_STATICS + ("width",)
_encode_bucket_gather_kernels = functools.partial(
    jax.jit, static_argnames=_GATHER_KERNEL_STATICS
)(_encode_bucket_gather_kernels_math)
_encode_bucket_gather_kernels_donate = functools.partial(
    jax.jit, static_argnames=_GATHER_KERNEL_STATICS, donate_argnums=(0,)
)(_encode_bucket_gather_kernels_math)


# ---------------------------------------------------------------------------
# Fixed-rate (entropy-off) mode: transform + table quantization only.
#
# The KV-cache workload keeps compressed blocks *fixed-size* so cold cache
# reads stay O(1) during decode — entropy coding would make block size
# data-dependent, and its rate win on narrow post-RMSNorm coefficient
# distributions is small anyway.  The fixed-rate path is the front half of
# the container pipeline (same window/DCT/quantize code, same calibrated
# tables riding the same EncodePlan cache) with the packer cut off: levels
# come back as a device-resident uint8 tensor whose shape is a pure
# function of the input shape.  Everything stays on device — no host
# staging, no drain; the caller owns the levels array.
# ---------------------------------------------------------------------------
def _encode_fixed_math(
    x: jnp.ndarray,  # f32[..., T] channel strips, T % n == 0
    tables: DeviceTables,
    *,
    n: int,
    e: int,
) -> jnp.ndarray:
    w = x.shape[-1] // n
    windows = x.reshape(x.shape[:-1] + (w, n))
    coeffs = dct.forward_dct(windows, e)
    return quantize(coeffs, tables.quant)  # uint8[..., W, e]


_encode_fixed = functools.partial(
    jax.jit, static_argnames=("n", "e")
)(_encode_fixed_math)


def _encode_fixed_kernels_math(
    x, tables, *, n, e, tuning_epoch=0
):
    # the Pallas DCT+quant tile with the exact-parity quantization arm:
    # levels are BIT-identical to the XLA arm (pinned in test_workloads),
    # so the kernels toggle changes which programs run — never bytes
    del tuning_epoch
    from repro.kernels import ops as kops

    w = x.shape[-1] // n
    windows = x.reshape(-1, n)
    levels = kops.dct_quant(windows, tables.quant, e=e, exact=True)
    return levels.astype(jnp.uint8).reshape(x.shape[:-1] + (w, e))


_encode_fixed_kernels = functools.partial(
    jax.jit, static_argnames=("n", "e", "tuning_epoch")
)(_encode_fixed_kernels_math)


# ---------------------------------------------------------------------------
# Encoded batches: streams stay on device until explicitly drained.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Slice:
    """Where signal i's stream lives: row ``row`` of bucket ``bucket``'s
    output arrays, plus the host-side container header fields."""

    bucket: int
    row: int
    num_windows: int
    signal_length: int
    n: int
    e: int
    l_max: int
    domain_id: int
    coding: Tuple[int, int, bool] = TRIVIAL_CODING


@dataclasses.dataclass(frozen=True)
class EncodedBucketParts:
    """One bucket's device-resident encode output, un-stitched.

    ``hi``/``lo``/``symlen`` are the per-chunk word runs
    ``[K, num_chunks, chunk_size]`` and ``words_per_chunk`` ``[K,
    num_chunks]`` — exactly what :func:`repro.core.symlen.
    pack_symlen_chunked_parts` produces per signal, batched over the
    bucket's ``K`` rows (rows past the real signals are batch padding and
    pack zero words).  ``unencodable`` is the bucket's device-side
    histogram-gap flag — per ROW (``bool[K]``; padding rows stay False) so
    a drain can demote the fault to a per-signal outcome — checked at
    drain.  ``shard``/``device`` record the
    scheduler placement (device None = default single-shard).  This is the
    shared stream contract between the encode engine and device-resident
    consumers (the transcode pipeline stitches these straight into decoder
    bucket streams via ``symlen.stitch_chunk_parts`` — no host round
    trip, each shard staying on its own device).

    Buckets encoded under a non-trivial coding (container v3) additionally
    carry per-signal coded-symbol counts ``ncoded`` and — when zero-plane
    suppression is on — the device-resident ``zrow``/``zcol`` masks; for
    trivial (v2) buckets all three stay ``None`` and the drain syncs
    exactly the arrays it always did.
    """

    plan_key: tuple  # (domain_id, n, e, l_max, coding)
    hi: jnp.ndarray  # uint32[K, B, C]
    lo: jnp.ndarray  # uint32[K, B, C]
    symlen: jnp.ndarray  # int32[K, B, C]
    words_per_chunk: jnp.ndarray  # int32[K, B]
    unencodable: jnp.ndarray  # bool[K]
    shard: int = 0
    device: object = None
    ncoded: Optional[jnp.ndarray] = None  # int32[K] (v3 only)
    zrow: Optional[jnp.ndarray] = None  # bool[K, Wp] (v3 zero planes)
    zcol: Optional[jnp.ndarray] = None  # bool[K, e] (v3 zero planes)

    @property
    def chunk_size(self) -> int:
        return int(self.hi.shape[2])

    @property
    def num_chunks(self) -> int:
        return int(self.hi.shape[1])

    def words_per_signal(self) -> jnp.ndarray:
        """Per-row word extents int32[K] — a device array (no sync)."""
        return jnp.sum(self.words_per_chunk, axis=1)


class EncodedBatch:
    """Result of :meth:`BatchEncoder.encode` — device-resident streams.

    ``to_host()`` performs the only host sync: every bucket's d2h copies
    start before any materializes (shard drains overlap), a histogram-gap
    check runs first (the device-side arm of the pack precheck), then
    numpy slicing into per-signal :class:`Container`\\ s (input order
    preserved).

    A batch drains **once**.  A second ``to_host()`` — or any drain after
    the device buffers were handed to a :class:`~repro.serving.transcode.
    Transcoder` — raises instead of silently re-syncing (the buffers may by
    then be donated or re-encoded under a different config, so a quiet
    second drain is a stale-data hazard).  Device-resident consumers read
    :meth:`device_parts` / :meth:`signal_slices` instead of draining.
    """

    def __init__(
        self,
        buckets: List[EncodedBucketParts],
        slices: List[Optional[_Slice]],
        pending_flags: Sequence[Tuple[tuple, jnp.ndarray]] = (),
        *,
        poisoned: Optional[Dict[int, Exception]] = None,
        quarantine: bool = False,
    ):
        self._buckets = buckets
        self._slices = slices
        # histogram-gap flags inherited from upstream device stages (a
        # transcode's source batch): checked at drain like our own
        self._pending_flags = list(pending_flags)
        # quarantine records: signals excluded before encoding (slice is
        # None at their index); the drain returns their typed error
        self._poisoned: Dict[int, Exception] = dict(poisoned or {})
        # quarantine drains demote a device-side histogram-gap flag from a
        # batch-fatal ValueError to a per-signal PoisonedContainerError
        self._quarantine = bool(quarantine)
        self._consumed: Optional[str] = None

    def __len__(self) -> int:
        return len(self._slices)

    def device_parts(self) -> List[EncodedBucketParts]:
        """The per-bucket chunk parts as device arrays — no host sync."""
        self._check_live("read device parts of")
        return list(self._buckets)

    def signal_slices(self) -> List[_Slice]:
        """Per-signal (input order) location + header metadata: which
        bucket/row holds signal i's chunk parts, plus the container header
        fields (num_windows, signal_length, n, e, l_max, domain_id)."""
        return list(self._slices)

    def block_until_ready(self) -> "EncodedBatch":
        for p in self._buckets:
            p.words_per_chunk.block_until_ready()
        return self

    def _check_live(self, verb: str) -> None:
        if self._consumed is not None:
            raise RuntimeError(
                f"cannot {verb} this EncodedBatch: {self._consumed}"
            )

    def _mark_consumed(self, reason: str) -> None:
        self._check_live("consume")
        self._consumed = reason

    def to_host(self) -> List[Any]:
        """Drain the batch into containers: one sync per bucket (all d2h
        copies in flight together), then a host-side stitch of each
        signal's chunk word-runs (chunk b of signal k contributes its
        row's first ``wpc[k, b]`` words).  The stitch is double-buffered
        (:func:`repro.serving.engine.fetch_to_host_stitched`): a worker
        concatenates bucket k's numpy chunk runs while bucket k+1's d2h
        copies land.

        A quarantined batch returns a :class:`~repro.serving.quarantine.
        PoisonedContainerError` at each poisoned signal's position instead
        of a :class:`Container` — never a batch-wide raise for per-signal
        faults.  Without quarantine, a device-side histogram-gap flag stays
        batch-fatal (the offline contract)."""
        self._check_live("drain")

        def _gap_error(key):
            # leave the batch live: a failed drain returned nothing, so
            # a retry must re-raise this error, not a bogus
            # "already drained" message
            return ValueError(
                f"encode batch for plan_key "
                f"(domain_id, n, e, l_max, coding)="
                f"{key} produced symbol(s) with no codeword (histogram "
                "gap in the Huffman book) — the stream would decode to "
                "garbage; recalibrate with Laplace smoothing or a "
                "complete codebook"
            )

        # upstream flags (a transcode's source batch) have no row->signal
        # mapping here, so they stay batch-fatal even under quarantine
        for key, flag in self._pending_flags:
            if bool(np.any(np.asarray(flag))):
                raise _gap_error(key)
        bucket_bad = [np.asarray(p.unencodable) for p in self._buckets]
        poisoned: Dict[int, Exception] = dict(self._poisoned)
        if self._quarantine:
            # demote the device-side gap flag to per-signal outcomes: the
            # flagged row's stream is garbage, but every other row packed
            # independently and drains byte-identically to a clean run
            from repro.serving.quarantine import (
                FAULT_HISTOGRAM_GAP,
                PoisonedContainerError,
            )

            for i, s in enumerate(self._slices):
                if s is None or i in poisoned:
                    continue
                if bool(bucket_bad[s.bucket][s.row]):
                    poisoned[i] = PoisonedContainerError(
                        "signal quantizes to symbol(s) with no codeword "
                        "(histogram gap in the Huffman book) under "
                        f"plan_key (domain_id, n, e, l_max, coding)="
                        f"{self._buckets[s.bucket].plan_key} — "
                        "recalibrate with Laplace smoothing or a complete "
                        "codebook",
                        index=i,
                        fault=FAULT_HISTOGRAM_GAP,
                    )
        else:
            for p, bad in zip(self._buckets, bucket_bad):
                if bool(np.any(bad)):
                    raise _gap_error(p.plan_key)

        per_bucket: List[List[Tuple[int, _Slice]]] = [
            [] for _ in self._buckets
        ]
        for i, s in enumerate(self._slices):
            if s is None or i in poisoned:
                continue
            per_bucket[s.bucket].append((i, s))

        def stitch_bucket(b: int, host: List[np.ndarray]):
            hi, lo, sl, wpc = host[:4]
            # v3 buckets drain (ncoded[, zrow, zcol]) after the stream parts
            ncoded = host[4] if len(host) > 4 else None
            zrow = host[5] if len(host) > 5 else None
            zcol = host[6] if len(host) > 6 else None
            stitched = []
            for i, s in per_bucket[b]:
                runs = [
                    (hi[s.row, c, :w], lo[s.row, c, :w], sl[s.row, c, :w])
                    for c, w in enumerate(wpc[s.row])
                    if w
                ]
                if runs:
                    hi_cat = np.concatenate([r[0] for r in runs])
                    lo_cat = np.concatenate([r[1] for r in runs])
                    sl_cat = np.concatenate([r[2] for r in runs])
                else:
                    hi_cat = lo_cat = np.empty(0, np.uint32)
                    sl_cat = np.empty(0, np.int32)
                pred_id, bands, zplanes = s.coding
                num_symbols = (
                    s.num_windows * s.e if ncoded is None
                    else int(ncoded[s.row])
                )
                stitched.append((i, Container(
                    words=symlen.u32_to_words(hi_cat, lo_cat),
                    symlen=sl_cat.astype(np.uint8),
                    num_symbols=num_symbols,
                    num_windows=s.num_windows,
                    signal_length=s.signal_length,
                    n=s.n,
                    e=s.e,
                    l_max=s.l_max,
                    domain_id=s.domain_id,
                    predictor=pred_id,
                    predict_bands=bands,
                    zero_planes=zplanes,
                    zrow=(
                        zrow[s.row, : s.num_windows].copy()
                        if zplanes else None
                    ),
                    zcol=zcol[s.row].copy() if zplanes else None,
                )))
            return stitched

        def drain_arrays(p: EncodedBucketParts):
            arrs = (p.hi, p.lo, p.symlen, p.words_per_chunk)
            if p.ncoded is not None:
                arrs += (p.ncoded,)
            if p.zrow is not None:
                arrs += (p.zrow, p.zcol)
            return arrs

        results = fetch_to_host_stitched(
            [drain_arrays(p) for p in self._buckets],
            stitch_bucket,
        )
        self._consumed = (
            "it was already drained by to_host() — hold on to the returned "
            "containers instead of draining twice"
        )
        out: List[Any] = [None] * len(self._slices)
        for i, err in poisoned.items():
            out[i] = err
        for stitched in results:
            for i, c in stitched:
                out[i] = c
        return out


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BatchEncoderStats:
    batches: int = 0
    signals: int = 0
    dispatches: int = 0  # fused bucket launches
    plan_hits: int = 0
    plan_misses: int = 0
    # per-dispatch padding/occupancy records (bounded history) — the
    # encode-side twin of BatchDecoderStats.bucket_pad
    bucket_pad: "deque[dict]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=1024)
    )


class BatchEncoder:
    """Encodes many signals in a bounded number of fused dispatches.

    Usage::

        enc = BatchEncoder()                      # chunked (fast) packing
        batch = enc.encode(signals, tables)       # tables: DomainTables, or
                                                  # {domain_id: DomainTables}
                                                  # + domain_ids=[...]
        containers = batch.to_host()              # one sync per bucket

    Signals are grouped by (domain, config) and sub-bucketed by power-of-two
    window and batch counts; each bucket is one fused dispatch.
    ``chunk_size=None`` selects *exact* mode (one packing chunk per
    signal): bit-identical output to ``core.codec.encode`` at the price of a
    length-S packing scan — that is what ``encode_device`` uses.
    ``pipeline``/``devices``/``prefetch`` are the shared engine-layer knobs
    (see :mod:`repro.serving.engine`): double-buffered staging and
    per-device bucket shards, neither of which changes output bytes.
    """

    def __init__(
        self,
        *,
        chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
        use_kernels: Optional[bool] = None,
        plan_cache_size: int = 32,
        pipeline: bool = True,
        devices: DevicesArg = "auto",
        prefetch: int = 2,
        policy: PolicyArg = None,
        cost_model: Optional[CostModel] = None,
    ):
        if chunk_size is not None and chunk_size <= 0:
            raise ValueError(f"chunk_size must be positive, got {chunk_size}")
        self.chunk_size = chunk_size
        # None defers to the process-wide FPTC_USE_KERNELS default; the
        # fused Pallas tile is bit-identical to the XLA path, so the toggle
        # changes which device programs run — never bytes
        if use_kernels is None:
            use_kernels = default_use_kernels()
        self.use_kernels = use_kernels
        self._plans = PlanCache(_build_encode_plan, plan_cache_size)
        self.scheduler = BucketScheduler(devices=devices, policy=policy)
        self.executor = PipelineExecutor(pipeline=pipeline, prefetch=prefetch)
        self.cost_model = (
            cost_model if cost_model is not None else default_cost_model()
        )
        self.stats = BatchEncoderStats()
        self._pending = SubmitBuffer()

    # -- incremental submission (the front-end's surface) -------------------
    def submit(
        self, signal: np.ndarray, domain_id: Optional[int] = None
    ) -> int:
        """Queue one signal for the next :meth:`flush` (thread-safe).

        The incremental half of the batch-at-once :meth:`encode` — see
        :meth:`BatchDecoder.submit`.  ``domain_id`` routes the signal's
        tables when the flush passes a mapping; row bytes never depend on
        which other signals share the flush.
        """
        return self._pending.submit((signal, domain_id))

    @property
    def pending(self) -> int:
        """Signals submitted since the last flush."""
        return len(self._pending)

    def flush(
        self, tables: TablesArg, *, quarantine: bool = False
    ) -> EncodedBatch:
        """Encode everything submitted since the last flush as one batch
        (submission order).  An empty flush is a no-op empty batch."""
        items = self._pending.take()
        signals = [s for s, _ in items]
        doms = [d for _, d in items]
        if all(d is None for d in doms):
            domain_ids = None
        elif any(d is None for d in doms):
            if not isinstance(tables, DomainTables):
                raise ValueError(
                    "flush with a {domain_id: DomainTables} mapping needs "
                    "every submit() to carry a domain_id"
                )
            domain_ids = [
                tables.domain_id if d is None else d for d in doms
            ]
        else:
            domain_ids = doms
        return self.encode(
            signals, tables, domain_ids=domain_ids, quarantine=quarantine
        )

    # -- plan management ---------------------------------------------------
    def _tables_for(self, domain_id: int, tables: TablesArg) -> DomainTables:
        if isinstance(tables, DomainTables):
            return tables
        try:
            return tables[domain_id]
        except KeyError:
            raise KeyError(
                f"no DomainTables registered for domain_id={domain_id}"
            ) from None

    def plan_for(self, tables: DomainTables, device=None) -> EncodePlan:
        cfg = tables.config
        key = (tables.domain_id, cfg.n, cfg.e, cfg.l_max, cfg.coding)
        return self._plans.get(tables, key, device)

    # -- fixed-rate (entropy-off) encode -----------------------------------
    def encode_fixed(
        self, x: jnp.ndarray, tables: DomainTables
    ) -> jnp.ndarray:
        """Transform + quantize only: ``f32[..., T]`` -> ``uint8[..., W, E]``.

        The KV-cache workload's O(1)-access mode: compressed size is a pure
        function of input shape (``E/N`` levels per sample, no sidecar), the
        calibrated tables ride the same :class:`EncodePlan` cache as the
        container path, and the result is a device-resident array — no host
        staging on the way in, no drain on the way out.  ``T`` (the last
        axis) must be a multiple of the domain's window size ``n``; leading
        axes are free (a KV block arrives as ``[B, H, D, T]`` channels).
        Decode with :meth:`BatchDecoder.decode_fixed`.

        The ``use_kernels`` toggle selects the Pallas DCT+quant tile in its
        exact-parity arm — levels are bit-identical either way.
        """
        plan = self.plan_for(tables)
        n, e = plan.n, plan.e
        if x.shape[-1] % n:
            raise ValueError(
                f"fixed-rate encode needs the time axis ({x.shape[-1]}) to "
                f"be a multiple of the window size n={n} — pad the block "
                "(fixed-size blocks are the point of this mode)"
            )
        x = jnp.asarray(x, jnp.float32)
        if self.use_kernels:
            levels = _encode_fixed_kernels(
                x, plan.tables, n=n, e=e,
                tuning_epoch=_autotune.epoch(),
            )
        else:
            levels = _encode_fixed(x, plan.tables, n=n, e=e)
        self.stats.dispatches += 1
        return levels

    # -- the batched encode ------------------------------------------------
    def encode(
        self,
        signals: Sequence[np.ndarray],
        tables: TablesArg,
        *,
        domain_ids: Optional[Sequence[int]] = None,
        quarantine: bool = False,
    ) -> EncodedBatch:
        """Encode a (possibly mixed-domain, mixed-length) batch of signals.

        ``domain_ids`` assigns each signal its domain when ``tables`` is a
        mapping; with a single :class:`DomainTables` every signal uses it.
        Returns an :class:`EncodedBatch`; nothing is synced to host here.
        ``quarantine=True`` demotes the device-side histogram-gap flag from
        batch-fatal to a typed per-signal outcome at drain.
        """
        signals = [np.asarray(s, dtype=np.float32).ravel() for s in signals]

        def stage(idxs, kp: int, wp: int, n: int, device) -> np.ndarray:
            x = np.zeros((kp, wp * n), dtype=np.float32)
            for row, i in enumerate(idxs):
                x[row, : signals[i].shape[0]] = signals[i]
            return x

        return self.encode_staged(
            [int(s.shape[0]) for s in signals], tables,
            domain_ids=domain_ids, stage=stage, quarantine=quarantine,
        )

    def encode_staged(
        self,
        lengths: Sequence[int],
        tables: TablesArg,
        *,
        stage,
        domain_ids: Optional[Sequence[int]] = None,
        pending_flags: Sequence[tuple] = (),
        shard_ids: Optional[Sequence[int]] = None,
        shard_devices: Optional[Dict[int, object]] = None,
        quarantine: bool = False,
    ) -> EncodedBatch:
        """The bucketing/dispatch core of :meth:`encode`, with the signal
        *staging* pluggable.

        ``stage(idxs, kp, wp, n, device)`` must produce the bucket's
        stacked signal matrix ``f32[kp, wp * n]`` — row ``r`` holds signal
        ``idxs[r]``'s samples followed by exact zeros, rows past
        ``len(idxs)`` all-zero — as a host/device array, **or** a
        :class:`~repro.serving.engine.GatherStage` describing the rows as
        slices of a device-resident flat tensor, in which case the gather
        happens *inside* the bucket's fused dispatch (the transcode
        pipeline's path).  Under pipelining the stage callback runs on the
        executor's staging worker, one bucket ahead of dispatch.
        Everything else — grouping, padding, chunk-size selection, shard
        assignment (``shard_ids`` pins signals to shards, with
        ``shard_devices`` mapping foreign shard ids to their devices when
        the pinning comes from another scheduler; default is a contiguous
        split per bucket), the fused dispatch, slice metadata — is this
        one code path, which is what makes device-staged encodes
        byte-identical to host-staged ones.
        """
        self.stats.batches += 1
        self.stats.signals += len(lengths)
        if not lengths:
            return EncodedBatch(
                [], [], pending_flags, quarantine=quarantine
            )
        if domain_ids is None:
            if not isinstance(tables, DomainTables):
                raise ValueError(
                    "domain_ids is required when tables is a "
                    "{domain_id: DomainTables} mapping"
                )
            domain_ids = [tables.domain_id] * len(lengths)
        if len(domain_ids) != len(lengths):
            raise ValueError(
                f"domain_ids has {len(domain_ids)} entries for "
                f"{len(lengths)} signals"
            )

        # group by ((domain, config), windows bucket), shard-split — one
        # fused dispatch per (group, shard); batch dim padded to a bucket
        # edge in the upload stage.  The window bucket follows the
        # scheduler's policy ladder, so a denser policy both shrinks row
        # padding AND splits fewer-window signals away from wide ones.
        keys = []
        per_tab: Dict[tuple, DomainTables] = {}
        all_windows: List[int] = []
        for length, dom in zip(lengths, domain_ids):
            tab = self._tables_for(dom, tables)
            cfg = tab.config
            num_windows = -(-length // cfg.n)
            all_windows.append(num_windows)
            key = (
                (dom, cfg.n, cfg.e, cfg.l_max, cfg.coding),
                self.scheduler.round(max(num_windows, 1)),
            )
            keys.append(key)
            per_tab.setdefault(key, tab)
        # cost-balanced shard split over predicted per-signal encode cost
        # (only worth computing when there is more than one shard and the
        # scheduler actually splits — pinned shard_ids bypass the split)
        item_costs = None
        if self.scheduler.num_shards > 1 and shard_ids is None:
            item_costs = [
                self.cost_model.signal_encode_cost(
                    w, e=key[0][2], n=key[0][1]
                )
                for w, key in zip(all_windows, keys)
            ]
        buckets = self.scheduler.buckets(
            keys, shard_ids=shard_ids, shard_devices=shard_devices,
            item_costs=item_costs,
        )

        slices: List[Optional[_Slice]] = [None] * len(lengths)
        for b, bucket in enumerate(buckets):
            plan_key, wp = bucket.key
            _, n, e, l_max, coding = plan_key
            for row, i in enumerate(bucket.items):
                slices[i] = _Slice(
                    bucket=b,
                    row=row,
                    num_windows=-(-lengths[i] // n),
                    signal_length=int(lengths[i]),
                    n=n,
                    e=e,
                    l_max=l_max,
                    domain_id=plan_key[0],
                    coding=coding,
                )

        def upload(bucket: Bucket):
            plan_key, wp = bucket.key
            _, n, e, _, _ = plan_key
            idxs = list(bucket.items)
            # pad batch dim to a bucket edge; pad rows pack 0 symbols
            kp = self.scheduler.round(len(idxs))
            counts = np.zeros((kp,), dtype=np.int32)
            for row, i in enumerate(idxs):
                counts[row] = -(-lengths[i] // n) * e
            put = putter(bucket.device)
            # shard-aware plan prefetch: the staging worker pays this
            # bucket's table/basis device_put, not its first dispatch
            self._plans.get(per_tab[bucket.key], plan_key, bucket.device)
            x = stage(idxs, kp, wp, n, bucket.device)
            if not isinstance(x, GatherStage):
                # place host AND device stage results: a stage returning an
                # uncommitted jnp array must still land on the bucket's
                # shard, or the fused jit would see operands on two devices
                x = put(x)
            return x, put(counts)

        def dispatch(bucket: Bucket, staged):
            x, counts = staged
            plan_key, wp = bucket.key
            plan = self._plans.get(
                per_tab[bucket.key], plan_key, bucket.device
            )
            n, e = plan.n, plan.e
            coding = plan_key[4]
            sp = wp * e
            chunk = sp if self.chunk_size is None else min(self.chunk_size, sp)
            if isinstance(x, GatherStage):
                donate = x.donate and _donation_supported(bucket.device)
                if self.use_kernels:
                    fused = (
                        _encode_bucket_gather_kernels_donate
                        if donate else _encode_bucket_gather_kernels
                    )
                    out = fused(
                        x.flat, x.starts, x.lens, counts, plan.tables,
                        width=wp * n, n=n, e=e, l_max=plan.l_max,
                        chunk_size=chunk, check_gaps=plan.has_gaps,
                        coding=coding, tuning_epoch=_autotune.epoch(),
                    )
                else:
                    fused = (
                        _encode_bucket_gather_donate
                        if donate else _encode_bucket_gather
                    )
                    out = fused(
                        x.flat, x.starts, x.lens, counts, plan.tables,
                        width=wp * n, n=n, e=e, chunk_size=chunk,
                        check_gaps=plan.has_gaps, coding=coding,
                    )
                kp = int(x.starts.shape[0])
            elif self.use_kernels:
                out = _encode_bucket_kernels(
                    x, counts, plan.tables,
                    n=n, e=e, l_max=plan.l_max, chunk_size=chunk,
                    check_gaps=plan.has_gaps,
                    coding=coding, tuning_epoch=_autotune.epoch(),
                )
                kp = int(x.shape[0])
            else:
                out = _encode_bucket(
                    x, counts, plan.tables,
                    n=n, e=e, chunk_size=chunk, check_gaps=plan.has_gaps,
                    coding=coding,
                )
                kp = int(x.shape[0])
            if coding == TRIVIAL_CODING:
                hi, lo, sl, wpc, bad = out
                ncoded = zrow = zcol = None
            else:
                hi, lo, sl, wpc, bad, ncoded, zrow, zcol = out
            self.stats.dispatches += 1
            self.stats.bucket_pad.append({
                "plan_key": plan_key,
                "shard": bucket.shard,
                "policy": self.scheduler.policy.name,
                "rows": len(bucket.items),
                "rows_padded": kp,
                "windows": sum(
                    -(-lengths[i] // n) for i in bucket.items
                ),
                "windows_padded": wp * kp,
            })
            return EncodedBucketParts(
                plan_key=plan_key, hi=hi, lo=lo, symlen=sl,
                words_per_chunk=wpc, unencodable=bad,
                shard=bucket.shard, device=bucket.device,
                ncoded=ncoded, zrow=zrow, zcol=zcol,
            )

        out_buckets = self.executor.run(buckets, upload, dispatch)
        self.stats.plan_hits = self._plans.hits
        self.stats.plan_misses = self._plans.misses
        return EncodedBatch(
            out_buckets, slices, pending_flags, quarantine=quarantine
        )

    def encode_to_host(
        self,
        signals: Sequence[np.ndarray],
        tables: TablesArg,
        *,
        domain_ids: Optional[Sequence[int]] = None,
    ) -> List[Container]:
        """Convenience: encode + drain in one call."""
        return self.encode(
            signals, tables, domain_ids=domain_ids
        ).to_host()


# ---------------------------------------------------------------------------
# Process-wide default encoders (codec.encode_device rides the exact one).
# ---------------------------------------------------------------------------
_DEFAULTS: Dict[Tuple[Optional[int], bool], BatchEncoder] = {}


def default_encoder(chunk_size: Optional[int] = None) -> BatchEncoder:
    """Shared encoder per (chunk size, resolved use_kernels).  ``None``
    chunk size (the default) is *exact* mode — bit-identical to the host
    encoder — which is what ``core.codec.encode_device`` rides; pass
    ``DEFAULT_CHUNK_SIZE`` (or any chunk) for the fast chunk-parallel
    packer.  The kernel toggle resolves from ``FPTC_USE_KERNELS`` *per
    call* (mirroring ``batch_decode.default_decoder``), so flipping the
    env mid-process switches which cached engine serves — bytes are
    identical either way.

    Being process-global, its plan cache keeps up to ``plan_cache_size``
    (32) recently-used DomainTables — and their device buffers — alive for
    the process lifetime (same trade as ``batch_decode.default_decoder``);
    callers churning many ephemeral table sets should hold their own
    :class:`BatchEncoder` and drop it when done."""
    use_kernels = default_use_kernels()
    key = (chunk_size, use_kernels)
    enc = _DEFAULTS.get(key)
    if enc is None:
        enc = _DEFAULTS[key] = BatchEncoder(
            chunk_size=chunk_size, use_kernels=use_kernels
        )
    return enc
