"""Batched bucketed decode engine: one fused dispatch for N containers.

The paper's asymmetry argument is about *server-side batch* decompression
throughput, but a per-container ``decode_device`` loop pays three taxes the
GPU codecs it compares against (GPU-Huffman, cuSZ+) never do:

  1. **recompilation** — seven container-specific static argnames mean XLA
     retraces for nearly every container in a heterogeneous archive;
  2. **table re-upload** — codebook + quant tables travel host->device per
     call;
  3. **host sync** — ``np.asarray`` blocks on every container.

This module removes all three:

  * **Shape bucketing.**  A batch's streams are concatenated and padded to
    power-of-two word/window/symlen-slot counts, so jit specializations are
    O(log sizes) instead of O(containers).  The formerly-static per-container
    quantities (word offsets, symbol counts, signal lengths) are either
    device arrays (the symlen sidecar drives all offsets) or host-side slice
    metadata — never trace constants.
  * **Concatenated-stream decode.**  SymLen words decode independently, so a
    whole batch is one word axis: the Pallas grid (or the XLA lane loop)
    sweeps every container in one dispatch, and compaction is a
    segment-aware scatter over one exclusive prefix-sum of the concatenated
    symlen sidecar (``core.symlen.compact_padded_scatter``) — container
    boundaries fall out of the segment sums for free.
  * **Persistent decode plans.**  Device tables and the dequant LUT upload
    once per (domain, config, shard device) into an LRU :class:`DecodePlan`
    cache; decoded samples stay on device inside a :class:`DecodedBatch`
    until an explicit ``.to_host()`` drains them.

Scheduling, double-buffered pipelining and multi-device sharding live in
the shared :mod:`repro.serving.engine` layer: host staging + h2d upload of
bucket k+1 overlap device compute of bucket k, and with several visible
devices each (domain, config) group's containers split into per-device
shards (streams are per-signal independent, so sharding is embarrassingly
parallel).  Neither changes the produced bytes — padding is invisible to
decoded samples and dispatch order is deterministic.

``core.codec.decode_device`` is a batch-of-one wrapper over this engine, so
every existing caller rides the same path.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from collections import deque
from typing import (
    Any,
    Callable,
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
from repro.core.codec import validate_container_tables
from repro.core.container import Container
from repro.core.quantize import (
    expand_coded_stream,
    quant_grid,
    recon_scale,
    unpredict_levels,
)
from repro.serving._plans import (
    TRIVIAL_CODING,
    PlanCache,
    normalize_plan_key,
)
from repro.serving.engine import (
    BucketScheduler,
    DevicesArg,
    PipelineExecutor,
    SubmitBuffer,
    default_use_kernels,
    fetch_to_host,
    member_positions,
    p2,
    putter,
    span,
    symlen_bucket,
)
from repro.tuning import autotune as _autotune
from repro.tuning.cost_model import CostModel, default_cost_model
from repro.tuning.policy import PolicyArg

__all__ = [
    "BatchDecoder",
    "DecodedBatch",
    "DecodePlan",
    "StreamGroup",
    "streams_from_containers",
    "default_decoder",
    "bucket_cache_size",
]

TablesArg = Union[DomainTables, Mapping[int, DomainTables]]


# ---------------------------------------------------------------------------
# Decode plans: per-(domain, config, shard) device state, uploaded once.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Device-resident decode state for one (domain, config) on one shard.

    Holds the Huffman/quant tables, the dequant LUT and its reconstruction
    bound as device arrays plus the statics that specialize the fused
    decode.  Everything here is
    batch-size independent: one plan serves every bucket shape on its
    device (``device=None`` is the single-shard default placement).
    """

    tables: DeviceTables
    lut: jnp.ndarray  # f32[E, 256] — quant_grid reconstruction LUT
    rscale: jnp.ndarray  # f32[] — recon_scale bound the iDCT splits against
    n: int
    e: int
    l_max: int
    domain_id: int
    device: object
    source: DomainTables  # host tables (kept so cache keys stay alive)
    # container-v3 coding triple (pred_id, predict_bands, zero_planes);
    # TRIVIAL_CODING decodes the classic v1/v2 stream
    coding: Tuple[int, int, bool] = TRIVIAL_CODING


def _build_decode_plan(tables: DomainTables, key, device) -> DecodePlan:
    domain_id, n, e, l_max, coding = normalize_plan_key(key)
    dev_tables = tables.device_tables()
    rscale = recon_scale(tables.quant)
    # the 256-level reconstruction LUT (quant_grid): dequantization becomes
    # an exact selection instead of per-symbol transcendentals, and —
    # because the fused Pallas kernel and the XLA path select from the SAME
    # materialized values — the two paths' float outputs are bit-identical
    lut, _ = quant_grid(tables.quant)
    if device is not None:
        dev_tables = jax.device_put(dev_tables, device)
        rscale = jax.device_put(rscale, device)
        lut = jax.device_put(lut, device)
    return DecodePlan(
        tables=dev_tables,
        lut=lut,
        rscale=rscale,
        n=n,
        e=e,
        l_max=l_max,
        domain_id=domain_id,
        device=device,
        source=tables,
        coding=coding,
    )


# ---------------------------------------------------------------------------
# The fused bucket decode — ONE jit specialization per bucket shape.
# ---------------------------------------------------------------------------
def _decode_bucket_phases(
    hi: jnp.ndarray,  # uint32[Wp]   (concatenated + zero-padded words)
    lo: jnp.ndarray,  # uint32[Wp]
    sl: jnp.ndarray,  # int32[Wp]    (0 on padding words)
    tables: DeviceTables,
    lut: jnp.ndarray,  # f32[E, 256] quant_grid reconstruction LUT
    rscale: jnp.ndarray,  # f32[] recon_scale of the plan's quant table
    v3: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    *,
    l_max: int,
    max_symlen: int,
    num_windows: int,  # bucketed (power-of-two) window count
    n: int,
    e: int,
    use_kernels: bool,
    coding: Tuple[int, int, bool] = TRIVIAL_CODING,
    tuning_epoch: int = 0,
) -> jnp.ndarray:
    """Decode one concatenated bucket to its samples f32[num_windows * N],
    window after window: each signal's samples are one contiguous run, so
    the drain hands strips back as slices of the 1-D host copy.

    Statics are *bucket shape only* — every per-container quantity rides in
    the device arrays (the symlen sidecar induces all word/symbol offsets via
    prefix sums) or stays host-side slice metadata.  Padding words carry
    symlen == 0 and therefore scatter no symbols; padding windows decode to
    don't-care rows that the host slicing never reads.

    Both arms dequantize by exact selection from the plan's materialized
    256-level LUT (``quant_grid``): faster than per-symbol transcendentals,
    and — since the fused kernel selects from the SAME values — it is what
    makes ``use_kernels=True`` bit-identical to this XLA arm.  With
    ``use_kernels=True`` the whole bucket lowers to exactly ONE
    ``pallas_call`` (the decode megakernel, ``kernels/decode_fused.py``) —
    no intermediate ``[max_symlen, W]`` tile, no separate compaction or
    iDCT program.

    Each arm carries named scopes, which reach the compiled ops'
    ``op_name`` metadata and so a profiler trace: the kernel arm one,
    ``fptc.decode.fused`` (the ``pallas_call`` and the operand layout
    around it), the XLA arm one per phase (``fptc.decode.huffman``,
    ``fptc.decode.compact``, ``fptc.decode.idct``).  Scopes are debug
    info, which JAX leaves out of the persistent compile cache's key; the
    program's name is in the key, and names the phases, so an executable
    compiled before the XLA arm's scopes existed is never loaded here.

    ``tuning_epoch`` is a pure retrace key: the kernel path resolves its
    Pallas block sizes from the tuning cache *at trace time*
    (``ops.decode_bucket_fused`` -> ``tuned_blocks``), so without it a
    bucket shape traced before ``tune()`` stored a better entry would keep
    its stale specialization forever.  Engines pass the cache epoch
    (bumped on every store) when ``use_kernels`` — the XLA arm always
    passes 0, since it has no tunables to invalidate.

    A non-trivial ``coding`` (container v3) inserts the inverse of the
    encoder's lossless pre-entropy stage between symbol unpack and LUT
    dequantization.  ``v3`` carries the host-precomputed expansion index
    ``idx int32[num_windows * e]`` (each cell's position in the dense coded
    stream, -1 = zero-plane-suppressed or bucket padding, expanding to the
    zero bin 128) and per-window segment starts ``seg int32[num_windows]``
    (the owning signal's first window; self for padding windows, whose
    degenerate segments unpredict back to 128).  ``num_symbols`` stays the
    ``num_windows * e`` capacity bound — ``idx`` never references a
    position at or beyond the bucket's true coded-symbol total, so the
    garbage tail is never read.  Exact inverse math:
    ``quantize.expand_coded_stream`` / ``unpredict_levels`` — the same
    reference functions the host decoder and the fused kernel epilogue
    call, which is what keeps all three bit-identical.
    """
    del tuning_epoch  # participates in the jit cache key only
    num_symbols = num_windows * e
    if use_kernels:
        from repro.kernels import ops as kops

        with jax.named_scope("fptc.decode.fused"):
            return kops.decode_bucket_fused(
                hi, lo, sl, tables, lut, rscale, v3,
                l_max=l_max, max_symlen=max_symlen, num_windows=num_windows,
                n=n, e=e, coding=coding,
            ).reshape(-1)
    syms = symlen.unpack_symlen(
        hi, lo, sl,
        tables.dec_limit, tables.dec_first, tables.dec_rank, tables.dec_syms,
        l_max=l_max, max_symlen=max_symlen, num_symbols=num_symbols,
    )
    if coding == TRIVIAL_CODING:
        levels = syms.reshape(num_windows, e).astype(jnp.int32)
    else:
        idx, seg = v3
        pred_id, bands, _ = coding
        grid = expand_coded_stream(syms, idx).reshape(num_windows, e)
        levels = unpredict_levels(
            grid.astype(jnp.uint32), seg, pred_id, bands
        ).astype(jnp.int32)
    with jax.named_scope("fptc.decode.idct"):
        coeffs = lut[jnp.arange(e, dtype=jnp.int32)[None, :], levels]
        return dct.inverse_dct(coeffs, n, scale=rscale).reshape(-1)


_decode_bucket = functools.partial(
    jax.jit,
    static_argnames=(
        "l_max", "max_symlen", "num_windows", "n", "e", "use_kernels",
        "coding", "tuning_epoch",
    ),
)(_decode_bucket_phases)


def _kernel_arm(plan: DecodePlan, words: int, num_windows: int,
                max_symlen: int) -> bool:
    """The arm of one bucket when the engine was left to choose
    (``use_kernels=None``): the Pallas megakernel where it runs compiled
    (a TPU backend) and takes the bucket, else the XLA arm.  Both give the
    same bits; a bucket the kernel would refuse never reaches it.  There is
    no lower size bound: on a TPU v5e the kernel arm took 5-14x less device
    time than the XLA arm at every bucket size measured, from one 5,000-
    sample strip (256 padded words) to 2 Mi padded words (PERF.md)."""
    from repro.kernels import ops as kops

    return (
        kops.on_tpu()
        and kops.decode_kernel_fits(
            words, num_windows, n=plan.n, e=plan.e, l_max=plan.l_max,
            max_symlen=max_symlen, coding=plan.coding,
        )
    )


def bucket_cache_size() -> Optional[int]:
    """Number of live XLA specializations of the fused bucket decode
    (None if this JAX version doesn't expose the jit cache)."""
    try:
        return _decode_bucket._cache_size()
    except AttributeError:  # pragma: no cover - older/newer jax
        return None


# ---------------------------------------------------------------------------
# Fixed-rate (entropy-off) mode: LUT dequantization + inverse DCT only.
# The decode half of BatchEncoder.encode_fixed — the KV-cache workload's
# O(1)-access path.  Levels arrive as a device-resident uint8 tensor (no
# container, no symlen sidecar) and samples come back device-resident.
# Dequantization selects from the plan's materialized quant_grid LUT, so
# fixed-rate samples are bit-identical to what the container path would
# reconstruct from the same levels.
# ---------------------------------------------------------------------------
def _decode_fixed_math(
    levels: jnp.ndarray,  # uint8[..., W, E]
    lut: jnp.ndarray,  # f32[E, 256]
    rscale: jnp.ndarray,  # f32[] recon_scale
    *,
    n: int,
    e: int,
) -> jnp.ndarray:
    idx = levels.astype(jnp.int32)
    coeffs = lut[jnp.arange(e, dtype=jnp.int32), idx]
    windows = dct.inverse_dct(coeffs, n, scale=rscale)  # [..., W, N]
    return windows.reshape(windows.shape[:-2] + (-1,))


_decode_fixed = functools.partial(
    jax.jit, static_argnames=("n", "e")
)(_decode_fixed_math)


def _decode_fixed_kernels_math(
    levels, lut, rscale, *, n, e, tuning_epoch=0
):
    # the staged Pallas dequant+iDCT tile: the same LUT selection and split
    # iDCT as the XLA arm, so the samples are the same bits
    del tuning_epoch
    from repro.kernels import ops as kops

    flat = levels.reshape(-1, e).astype(jnp.int32)
    windows = kops.idct_dequant(flat, lut, rscale, n=n)
    return windows.reshape(levels.shape[:-2] + (-1,))


_decode_fixed_kernels = functools.partial(
    jax.jit, static_argnames=("n", "e", "tuning_epoch")
)(_decode_fixed_kernels_math)


# ---------------------------------------------------------------------------
# Decoded batches: outputs stay on device until explicitly drained.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Slice:
    """Where container i's samples live: ``signal_length`` samples from
    ``start`` of group ``group``'s flat sample tensor."""

    group: int
    start: int
    signal_length: int


_SAMPLE_BYTES = np.dtype(np.float32).itemsize  # decoded samples are f32


class DecodedBatch:
    """Result of :meth:`BatchDecoder.decode` — device-resident samples.

    Each bucket's decode program emits its samples flat, window after
    window, so every signal is one contiguous run of its bucket's tensor.
    ``to_host()`` performs the only host sync: every bucket's d2h copy is
    started before any is materialized (so shard drains overlap), then
    each signal is handed back, in input order, as a view of its bucket's
    host array — no sample is copied after the d2h.

    A drained signal is a float32, C-contiguous, **read-only** array of
    shape ``(signal_length,)``.  It keeps its bucket's (padded) host array
    alive while it is held, never overlaps another signal of the same
    drain, and is never changed by a later decode or drain.  A caller that
    wants to own or write a signal takes ``np.array(y)``.

    A quarantined decode (``BatchDecoder.decode(..., quarantine=True)``)
    carries a ``poisoned`` record per excluded signal: its slice is None,
    ``to_host()`` returns the typed
    :class:`~repro.serving.quarantine.PoisonedContainerError` at that
    position, and ``device_signal(i)`` raises it.
    """

    def __init__(
        self,
        groups: List[jnp.ndarray],
        widths: List[int],
        slices: List[Optional[_Slice]],
        *,
        poisoned: Optional[Dict[int, Exception]] = None,
        stats: Optional["BatchDecoderStats"] = None,
    ):
        self._groups = groups  # per group: f32[num_windows_p * N] on device
        self._widths = widths  # per group: N, the samples of a window
        self._slices = slices
        self._poisoned: Dict[int, Exception] = dict(poisoned or {})
        self._stats = stats  # the decoder's, counting the drained bytes

    def __len__(self) -> int:
        return len(self._slices)

    @property
    def device_samples(self) -> List[jnp.ndarray]:
        """The per-bucket flat sample tensors (device arrays), padding
        windows included: what ``to_host()`` copies."""
        return list(self._groups)

    @property
    def device_windows(self) -> List[jnp.ndarray]:
        """The per-bucket window tensors f32[num_windows_p, N] (device
        arrays; each a reshape of its flat tensor, made on the device)."""
        return [g.reshape(-1, n) for g, n in zip(self._groups, self._widths)]

    def device_signal(self, i: int) -> jnp.ndarray:
        """Container i's reconstructed signal as a device array (lazy).
        Raises the typed per-request error for a quarantined signal."""
        s = self._slices[i]
        if s is None:
            raise self._poisoned[i]
        return self._groups[s.group][s.start:s.start + s.signal_length]

    def block_until_ready(self) -> "DecodedBatch":
        for g in self._groups:
            g.block_until_ready()
        return self

    def to_host(self) -> List[Any]:
        """Drain the batch: one device->host transfer per bucket, all
        copies in flight before the first materializes.  Each signal comes
        back as a read-only view of its bucket's host array (the class
        docstring states the contract); take ``np.array(y)`` to own one.
        Quarantined positions hold their typed per-request error instead
        of samples — a poisoned signal never raises batch-wide here."""
        with span("fptc.drain.d2h",
                  bytes=lambda: sum(g.nbytes for g in self._groups)):
            host = fetch_to_host(self._groups)
        copied = 0
        for g, h in enumerate(host):
            if not h.flags.c_contiguous:
                # a strided host copy would hand back strided strips: make
                # the bucket contiguous once, and count the copy
                host[g] = np.ascontiguousarray(h)
                host[g].flags.writeable = False
                copied += host[g].nbytes
        nbytes = _SAMPLE_BYTES * sum(
            s.signal_length for s in self._slices if s is not None)
        out: List[Any] = []
        with span("fptc.drain.stitch", bytes=nbytes):
            for i, s in enumerate(self._slices):
                if s is None:
                    out.append(self._poisoned[i])
                    continue
                # a basic slice of a 1-D array: always a view, never a copy
                out.append(host[s.group][s.start:s.start + s.signal_length])
        if self._stats is not None:
            self._stats.count_drain(nbytes, copied)
        return out


# ---------------------------------------------------------------------------
# Pre-concatenated device streams: the engine's input contract, exposed.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StreamGroup:
    """One (domain, config) group's concatenated SymLen stream, ready for a
    fused bucket decode — the representation :meth:`BatchDecoder.decode`
    builds internally from host containers, made public so device-resident
    producers (the transcode pipeline's ``symlen.stitch_chunk_parts``
    output) can feed the decoder WITHOUT materializing containers or
    touching the host.

    ``hi``/``lo``/``symlen`` are device (or host) word arrays of one shared
    length; trailing padding words must carry ``symlen == 0`` (they then
    contribute no symbols).  ``members`` lists each signal's
    ``(num_windows, signal_length)`` in stream order — the word->symbol
    prefix sums recover everything else.  ``max_symlen`` is a host-side
    bound on the per-word symbol count (<= 64); exact is best (fewest slot
    iterations) but any safe bound decodes correctly.  ``device``/``shard``
    place the group's fused dispatch (None = default single-shard
    placement); ``live_words`` is the host-known true word count when the
    producer has it (container staging does; device-resident stitches
    don't) — it feeds the padding-occupancy stats only.

    v3 groups (plan key with a non-trivial coding triple) additionally
    carry the host-precomputed coded-stream expansion: ``v3_idx``
    ``int32[num_windows_bucketed * e]`` (dense-stream position per grid
    cell, -1 = suppressed/padding) and ``v3_seg``
    ``int32[num_windows_bucketed]`` (per-window segment start for the
    unpredictor), both built by ``symlen.v3_expand_index`` at the
    *scheduler-rounded* window count so the arrays are bucket-shaped (no
    per-batch retrace).
    """

    plan_key: tuple  # (domain_id, n, e, l_max, coding)
    hi: jnp.ndarray  # uint32[Wp]
    lo: jnp.ndarray  # uint32[Wp]
    symlen: jnp.ndarray  # int32[Wp]
    max_symlen: int
    members: Sequence[Tuple[int, int]]  # (num_windows, signal_length)
    device: object = None
    shard: int = 0
    live_words: Optional[int] = None
    v3_idx: Optional[jnp.ndarray] = None  # int32[NWp * e]
    v3_seg: Optional[jnp.ndarray] = None  # int32[NWp]

    @property
    def total_windows(self) -> int:
        return sum(nw for nw, _ in self.members)


def _stage_container_group(
    members: Sequence[Container],
    key,
    device,
    shard: int,
    rounder: Callable[[int], int] = p2,
) -> StreamGroup:
    """Host-stage one bucket: concatenate member streams into bucket-edge
    padded word arrays (``rounder`` — the scheduler policy's ``round``;
    power-of-two by default) and upload them (to ``device`` when
    sharded).  For a v3 plan key the coded-stream expansion index/segment
    arrays are built here too, at the rounded window count the dispatch
    will use (padding windows expand to the zero bin and unpredict to
    themselves)."""
    total_words = sum(c.num_words for c in members)
    wp = rounder(max(total_words, 1))
    hi = np.zeros(wp, dtype=np.uint32)
    lo = np.zeros(wp, dtype=np.uint32)
    sl = np.zeros(wp, dtype=np.int32)
    woff = 0
    for c in members:
        chi, clo = c.words_u32()
        hi[woff:woff + c.num_words] = chi
        lo[woff:woff + c.num_words] = clo
        sl[woff:woff + c.num_words] = c.symlen
        woff += c.num_words
    put = putter(device)
    key = normalize_plan_key(key)
    v3_idx = v3_seg = None
    if key[4] != TRIVIAL_CODING:
        e = key[2]
        nwp = rounder(max(sum(c.num_windows for c in members), 1))
        idx, seg = symlen.v3_expand_index(
            [(c.num_windows, c.zrow, c.zcol) for c in members],
            e, total_windows=nwp,
        )
        v3_idx = put(idx)
        v3_seg = put(seg)
    return StreamGroup(
        plan_key=key,
        hi=put(hi),
        lo=put(lo),
        symlen=put(sl),
        max_symlen=max((c.max_symlen for c in members), default=0),
        members=[(c.num_windows, c.signal_length) for c in members],
        device=device,
        shard=shard,
        live_words=total_words,
        v3_idx=v3_idx,
        v3_seg=v3_seg,
    )


def streams_from_containers(
    containers: Sequence[Container],
    policy: PolicyArg = None,
) -> Tuple[List[StreamGroup], List[int]]:
    """Group host containers by plan_key and concatenate their streams
    (single-shard, default placement — the eager public form of the
    staging :meth:`BatchDecoder.decode` pipelines lazily).  ``policy``
    picks the word-padding ladder (None = ``FPTC_BUCKET_POLICY``).

    Returns the :class:`StreamGroup` list (group order = first appearance;
    members in input order within a group) plus, per input container, its
    member position in the groups' flattened order — what
    :meth:`BatchDecoder.decode` uses to restore caller order after
    :meth:`BatchDecoder.decode_streams`.
    """
    containers = list(containers)
    scheduler = BucketScheduler(devices=None, policy=policy)
    buckets = scheduler.buckets([c.plan_key for c in containers])
    groups = [
        _stage_container_group(
            [containers[i] for i in b.items], b.key, b.device, b.shard,
            scheduler.round,
        )
        for b in buckets
    ]
    return groups, member_positions(buckets, len(containers))


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BatchDecoderStats:
    batches: int = 0
    containers: int = 0
    dispatches: int = 0  # fused bucket launches
    kernel_dispatches: int = 0  # of which on the Pallas megakernel arm
    plan_hits: int = 0
    plan_misses: int = 0
    quarantined: int = 0  # signals poisoned out of quarantine=True batches
    drain_bytes: int = 0  # sample bytes handed back by to_host()
    # host bytes to_host() copied to hand them back: a bucket whose host
    # copy came back strided is made contiguous; 0 where strips are views
    drain_bytes_copied: int = 0
    # per-dispatch padding/occupancy records (bounded history) — feeds the
    # bench JSON's bucket-waste report and the half-octave bucket-policy
    # decision (ROADMAP)
    bucket_pad: "deque[dict]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=1024)
    )
    # drains run on the callers' threads (the frontend's drain pool among
    # them), so the drain counters are updated under a lock
    _drain_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def count_drain(self, nbytes: int, copied: int = 0) -> None:
        """Add one drain's handed-back and host-copied bytes."""
        with self._drain_lock:
            self.drain_bytes += nbytes
            self.drain_bytes_copied += copied


class BatchDecoder:
    """Decodes many containers in a bounded number of fused dispatches.

    Usage::

        dec = BatchDecoder()
        batch = dec.decode(containers, tables)   # tables: DomainTables, or
                                                 # {domain_id: DomainTables}
        signals = batch.to_host()                # one sync, input order;
                                                 # read-only views of the
                                                 # drained bucket arrays
        mine = np.array(signals[0])              # a writable copy to own

    Containers are grouped by :attr:`Container.plan_key` (domain, config);
    each group's streams are concatenated word-wise and padded to the
    ``policy`` ladder's bucket edges (``p2`` by default /
    ``FPTC_BUCKET_POLICY``), then decoded by one :func:`_decode_bucket`
    launch.  A mixed archive of hundreds of containers therefore costs
    #distinct-plan-keys x #shards dispatches and O(density * log sizes)
    compilations, total.  ``pipeline`` double-buffers host
    staging/upload against device compute; ``devices`` controls sharding
    (``"auto"`` = all visible local devices, ``None`` = single default
    device), with the per-device split cost-balanced over
    ``cost_model``'s per-container decode-cost prediction — policy,
    pipelining and sharding all change scheduling only, never bytes.

    ``use_kernels`` picks the program that decodes a bucket: ``True`` the
    Pallas megakernel, ``False`` the XLA arm, ``None`` (the default) a
    choice per bucket — the megakernel on a TPU where it takes the bucket
    (trivial coding, within the VMEM budget), else the XLA arm; the arms
    give the same bits.  ``FPTC_USE_KERNELS=1`` turns ``None`` into
    ``True``.  ``stats.bucket_pad`` records each dispatch's ``arm`` and
    the ``device`` it ran on; while the profiler traces, each dispatch is
    also an ``fptc.dispatch`` host span.
    """

    def __init__(
        self,
        *,
        use_kernels: Optional[bool] = None,
        plan_cache_size: int = 32,
        pipeline: bool = True,
        devices: DevicesArg = "auto",
        prefetch: int = 2,
        policy: PolicyArg = None,
        cost_model: Optional[CostModel] = None,
    ):
        # FPTC_USE_KERNELS=1 forces the kernel arm on engines left at None
        # (the kernels-interpret CI leg flips every engine onto the fused
        # path); otherwise None picks the arm per bucket (_kernel_arm)
        if use_kernels is None and default_use_kernels():
            use_kernels = True
        self.use_kernels = use_kernels
        self._plans = PlanCache(_build_decode_plan, plan_cache_size)
        self.scheduler = BucketScheduler(devices=devices, policy=policy)
        self.executor = PipelineExecutor(pipeline=pipeline, prefetch=prefetch)
        self.cost_model = (
            cost_model if cost_model is not None else default_cost_model()
        )
        self.stats = BatchDecoderStats()
        self._pending = SubmitBuffer()

    # -- incremental submission (the front-end's surface) -------------------
    def submit(self, container: Container) -> int:
        """Queue one container for the next :meth:`flush` (thread-safe).

        The incremental half of the batch-at-once :meth:`decode`: a serving
        front-end admits containers one at a time as requests arrive, then
        flushes them as ONE fused-bucket batch when its micro-batcher
        decides.  Returns the container's index in flush order — batch
        formation changes *when* the bucket dispatches, never the bytes any
        member decodes to.
        """
        return self._pending.submit(container)

    @property
    def pending(self) -> int:
        """Containers submitted since the last flush."""
        return len(self._pending)

    def flush(
        self, tables: TablesArg, *, quarantine: bool = False
    ) -> DecodedBatch:
        """Decode everything submitted since the last flush as one batch
        (submission order).  An empty flush is a no-op empty batch."""
        return self.decode(self._pending.take(), tables, quarantine=quarantine)

    # -- plan management ---------------------------------------------------
    def _tables_for(self, key, tables: TablesArg) -> DomainTables:
        if isinstance(tables, DomainTables):
            return tables
        domain_id = key[0]
        try:
            return tables[domain_id]
        except KeyError:
            raise KeyError(
                f"no DomainTables registered for domain_id={domain_id}"
            ) from None

    def _plan_for_key(self, key, tables: TablesArg, device=None) -> DecodePlan:
        key = normalize_plan_key(key)
        tab = self._tables_for(key, tables)
        validate_container_tables(key, tab)
        return self._plans.get(tab, key, device)

    def plan_for(
        self, container: Container, tables: TablesArg
    ) -> DecodePlan:
        return self._plan_for_key(container.plan_key, tables)

    # -- fixed-rate (entropy-off) decode -----------------------------------
    def decode_fixed(
        self,
        levels: jnp.ndarray,
        tables: DomainTables,
        *,
        length: Optional[int] = None,
        dtype=jnp.float32,
    ) -> jnp.ndarray:
        """Inverse of :meth:`BatchEncoder.encode_fixed`:
        ``uint8[..., W, E]`` levels -> ``[..., T]`` samples (``T = W * n``,
        trimmed to ``length`` when given).

        Dequantization is an exact selection from the plan's 256-level
        ``quant_grid`` LUT — the same values the container decode path
        reconstructs — followed by the MXU iDCT.  Everything stays device-
        resident; tables/LUT ride the persistent :class:`DecodePlan`
        cache, so repeated cold-block reads pay zero re-uploads.
        """
        cfg = tables.config
        key = (tables.domain_id, cfg.n, cfg.e, cfg.l_max, cfg.coding)
        plan = self._plan_for_key(key, tables)
        n, e = plan.n, plan.e
        if levels.shape[-1] != e:
            raise ValueError(
                f"levels last axis {levels.shape[-1]} != domain E={e}"
            )
        if self.use_kernels:
            x = _decode_fixed_kernels(
                levels, plan.lut, plan.rscale, n=n, e=e,
                tuning_epoch=_autotune.epoch(),
            )
        else:
            x = _decode_fixed(levels, plan.lut, plan.rscale, n=n, e=e)
        self.stats.dispatches += 1
        if length is not None:
            x = x[..., :length]
        return x.astype(dtype)

    # -- the batched decode ------------------------------------------------
    def decode(
        self,
        containers: Sequence[Any],
        tables: TablesArg,
        *,
        quarantine: bool = False,
    ) -> DecodedBatch:
        """Decode a (possibly mixed-domain, mixed-length) batch of containers.

        Returns a :class:`DecodedBatch`; nothing is synced to host here.

        ``quarantine=True`` is the serving contract: items may be raw bytes
        or parsed :class:`Container` objects, each is wire-format + deep
        validated against ``tables`` before staging, and a poisoned item is
        excluded from its bucket instead of raising batch-wide — the clean
        subset decodes byte-identically to a clean batch and the poisoned
        slot's :class:`~repro.serving.quarantine.PoisonedContainerError`
        rides the returned batch.  Without quarantine every item must be a
        :class:`Container` and any fault raises (the offline contract).
        """
        containers = list(containers)
        self.stats.batches += 1
        self.stats.containers += len(containers)

        poisoned: Dict[int, Exception] = {}
        clean_pos = list(range(len(containers)))
        if quarantine:
            from repro.serving.quarantine import validate_or_poison

            clean_pos, clean = [], []
            for i, item in enumerate(containers):
                c, err = validate_or_poison(item, i, tables)
                if err is not None:
                    poisoned[i] = err
                else:
                    clean_pos.append(i)
                    clean.append(c)
            total = len(containers)
            self.stats.quarantined += len(poisoned)
            containers = clean

        if not containers:
            slices: List[Optional[_Slice]] = (
                [None] * total if quarantine else []
            )
            return DecodedBatch(
                [], [], slices, poisoned=poisoned, stats=self.stats)

        if isinstance(tables, DomainTables):
            # a single DomainTables means "decode everything with these" —
            # only coherent for a single-domain batch (otherwise some
            # containers would silently decode with the wrong tables, or die
            # in an opaque shape error when configs differ)
            domains = {c.domain_id for c in containers}
            if len(domains) > 1:
                raise ValueError(
                    f"mixed-domain batch (domain_ids={sorted(domains)}) "
                    "needs a {domain_id: DomainTables} mapping, not a "
                    "single DomainTables"
                )

        with span("fptc.schedule"):
            # with several shards, split each group at cost-balanced (not
            # equal-count) boundaries over the model's per-container decode
            # cost — container metadata carries everything the model needs
            item_costs = None
            if self.scheduler.num_shards > 1:
                item_costs = [
                    self.cost_model.signal_decode_cost(
                        c.num_words, c.num_windows,
                        e=c.e, n=c.n, max_symlen=symlen_bucket(c.max_symlen),
                    )
                    for c in containers
                ]
            buckets = self.scheduler.buckets(
                [c.plan_key for c in containers], item_costs=item_costs
            )
            member_pos = member_positions(buckets, len(containers))
            # staging stays lazy: the executor's worker runs the host concat +
            # h2d upload of bucket k+1 while bucket k's decode dispatches
            lazy = [
                functools.partial(
                    _stage_container_group,
                    [containers[i] for i in b.items], b.key, b.device, b.shard,
                    self.scheduler.round,
                )
                for b in buckets
            ]
        batch = self.decode_streams(lazy, tables)
        # decode_streams orders slices by (group, member); restore the
        # caller's container order
        slices = [batch._slices[member_pos[i]] for i in range(len(containers))]
        if quarantine:
            full: List[Optional[_Slice]] = [None] * total
            for j, i in enumerate(clean_pos):
                full[i] = slices[j]
            slices = full
        return DecodedBatch(batch._groups, batch._widths, slices,
                            poisoned=poisoned, stats=self.stats)

    def decode_streams(
        self,
        groups: Sequence[Union[StreamGroup, Callable[[], StreamGroup]]],
        tables: TablesArg,
    ) -> DecodedBatch:
        """Decode pre-concatenated (device- or host-resident) bucket streams.

        This is :meth:`decode` minus the container unpacking/concatenation:
        each :class:`StreamGroup` is one fused dispatch, nothing is synced
        to host, and device-array inputs stay on device end to end — the
        entry point the transcode pipeline uses to feed an
        ``EncodedBatch``'s stitched chunk parts straight back through the
        decoder.  A group may also be a zero-argument callable producing
        its :class:`StreamGroup` — the executor's staging contract, letting
        the host concat + upload of later groups overlap earlier groups'
        decode.  The returned batch's signals are ordered group by group,
        following each group's ``members`` order.
        """
        groups = list(groups)

        def upload(g) -> StreamGroup:
            grp = g() if callable(g) else g
            put = putter(grp.device)
            # shard-aware plan prefetch: build/upload this bucket's decode
            # plan (tables + LUT device_put) from the staging
            # worker, so the first dispatch on each shard doesn't pay it —
            # PlanCache.get is thread-safe and the factory only transfers
            self._plan_for_key(tuple(grp.plan_key), tables, grp.device)
            return dataclasses.replace(
                grp, hi=put(grp.hi), lo=put(grp.lo), symlen=put(grp.symlen),
                v3_idx=(
                    put(grp.v3_idx) if grp.v3_idx is not None else None
                ),
                v3_seg=(
                    put(grp.v3_seg) if grp.v3_seg is not None else None
                ),
            )

        def dispatch(g, grp: StreamGroup) -> Tuple[jnp.ndarray,
                                                   StreamGroup]:
            plan = self._plan_for_key(
                tuple(grp.plan_key), tables, grp.device
            )
            wp = int(grp.hi.shape[0])
            num_windows = self.scheduler.round(max(grp.total_windows, 1))
            max_symlen = symlen_bucket(grp.max_symlen)
            use_kernels = self.use_kernels
            if use_kernels is None:
                use_kernels = _kernel_arm(plan, wp, num_windows, max_symlen)
            if plan.coding != TRIVIAL_CODING:
                if grp.v3_idx is None or grp.v3_seg is None:
                    raise ValueError(
                        "v3-coded StreamGroup is missing its "
                        "v3_idx/v3_seg expansion arrays (build them with "
                        "symlen.v3_expand_index at the scheduler-rounded "
                        "window count)"
                    )
                v3 = (grp.v3_idx, grp.v3_seg)
            else:
                v3 = None
            # the jax device id the program is enqueued on; 0 stands for
            # the default placement of the single-shard path
            device = 0 if grp.device is None else grp.device.id
            arm = "pallas" if use_kernels else "xla"
            with span("fptc.dispatch", shard=grp.shard, device=device,
                      arm=arm, words_padded=wp):
                samples = _decode_bucket(
                    grp.hi,
                    grp.lo,
                    grp.symlen,
                    plan.tables,
                    plan.lut,
                    plan.rscale,
                    v3,
                    l_max=plan.l_max,
                    max_symlen=max_symlen,
                    num_windows=num_windows,
                    n=plan.n,
                    e=plan.e,
                    use_kernels=use_kernels,
                    coding=plan.coding,
                    # retrace when the tuning cache learns better block
                    # sizes (kernel path only — the XLA arm has no tunables)
                    tuning_epoch=_autotune.epoch() if use_kernels else 0,
                )
            self.stats.dispatches += 1
            self.stats.kernel_dispatches += int(use_kernels)
            self.stats.bucket_pad.append({
                "plan_key": tuple(grp.plan_key),
                "shard": grp.shard,
                "device": device,
                "policy": self.scheduler.policy.name,
                "arm": arm,
                "words": grp.live_words,
                "words_padded": wp,
                "windows": grp.total_windows,
                "windows_padded": num_windows,
            })
            return samples, grp

        results = self.executor.run(groups, upload, dispatch)

        out_groups: List[jnp.ndarray] = []
        widths: List[int] = []
        slices: List[_Slice] = []
        for g, (samples, grp) in enumerate(results):
            n = grp.plan_key[1]  # (domain_id, n, e, l_max, coding)
            start = 0
            for num_windows, signal_length in grp.members:
                slices.append(_Slice(
                    group=g, start=start, signal_length=signal_length,
                ))
                start += num_windows * n
            out_groups.append(samples)
            widths.append(n)

        self.stats.plan_hits = self._plans.hits
        self.stats.plan_misses = self._plans.misses
        return DecodedBatch(out_groups, widths, slices, stats=self.stats)

    def decode_to_host(
        self, containers: Sequence[Container], tables: TablesArg
    ) -> List[np.ndarray]:
        """Convenience: decode + drain in one call (read-only views, as
        :meth:`DecodedBatch.to_host`)."""
        return self.decode(containers, tables).to_host()


# ---------------------------------------------------------------------------
# Process-wide default decoders (codec.decode_device rides these).
# ---------------------------------------------------------------------------
_DEFAULTS: Dict[Optional[bool], BatchDecoder] = {}


def default_decoder(use_kernels: Optional[bool] = None) -> BatchDecoder:
    """Shared decoder per ``use_kernels`` as :class:`BatchDecoder` resolves
    it (``None``: the arm per bucket, unless FPTC_USE_KERNELS forces it)."""
    if use_kernels is None and default_use_kernels():
        use_kernels = True
    dec = _DEFAULTS.get(use_kernels)
    if dec is None:
        dec = _DEFAULTS[use_kernels] = BatchDecoder(use_kernels=use_kernels)
    return dec
