"""Shared serving-engine layer: bucket scheduling, pipelined execution,
multi-device sharding.

PR 1-3 grew three engines (batched decode, batched encode, device-resident
transcode) that each re-implemented the same machinery: group work items by
(domain, config) key, pad shapes to power-of-two buckets, loop bucket ->
host stage -> h2d upload -> fused jit dispatch, then drain once.  This
module owns that machinery so the engines are thin *stage definitions*:

  * :class:`BucketScheduler` — grouping (first-appearance key order),
    power-of-two / symlen-slot bucket rounding, and shard assignment: with
    more than one visible device, each key group's members split into
    contiguous per-device shards (per-signal streams are independent, so
    sharding the batch axis is embarrassingly parallel — no collectives,
    just per-shard placement).
  * :class:`PipelineExecutor` — runs per-bucket work as stage(upload) ->
    stage(dispatch) with double buffering: a single staging worker runs
    host staging + h2d upload of bucket k+1 while the main thread
    dispatches bucket k (XLA dispatch is async, so device compute of
    bucket k overlaps both).  ``fetch_to_host`` is the drain-side twin: it
    starts every bucket's d2h copy before materializing any of them, so
    drains overlap each other and any still-running dispatch.
  * :class:`GatherStage` — the device-staging contract: an encode bucket's
    signal matrix materializes *inside* the bucket's fused dispatch as a
    batched ``dynamic_slice`` gather out of decoded window tensors
    (optionally donating the source buffer on its last use).
  * :func:`span` — the serving path's host spans (``fptc.*``), profiler
    events that exist only while ``jax.profiler`` is tracing.

Pipelining and sharding change *when* and *where* buckets run — never what
bytes they produce: bucket padding is invisible to decoded samples and
per-row packing, dispatch order is deterministic, and the synchronous
single-device path is the degenerate case (one shard, no prefetch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import (
    Any,
    Callable,
    Dict,
    Hashable,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.tuning.policy import BucketPolicy, PolicyArg

__all__ = [
    "MAX_SYMLEN_CAP",
    "p2",
    "symlen_bucket",
    "serving_devices",
    "default_use_kernels",
    "putter",
    "Bucket",
    "BucketScheduler",
    "PipelineExecutor",
    "ExecutorStats",
    "span",
    "GatherStage",
    "SubmitBuffer",
    "fetch_to_host",
    "fetch_to_host_stitched",
]

MAX_SYMLEN_CAP = 64  # a 64-bit word holds at most 64 one-bit codes

DevicesArg = Union[None, str, Sequence[Any]]


def p2(x: int) -> int:
    """Next power of two (>= 1) — the bucket rounding."""
    return 1 << max(int(x) - 1, 0).bit_length()


def symlen_bucket(x: int) -> int:
    """Round the slot-loop trip count up to a multiple of 8 (cap 64).

    The decode cost is linear in this number, so power-of-two rounding would
    waste up to 2x slot iterations (e.g. 33 -> 64); multiples of 8 bound the
    waste at <8 slots while keeping specializations to at most 8 variants.
    """
    return min(-(-max(int(x), 1) // 8) * 8, MAX_SYMLEN_CAP)


def default_use_kernels() -> bool:
    """Process-wide default for the engines' ``use_kernels`` stage toggle.

    Engines constructed with ``use_kernels=None`` resolve it here, so one
    environment variable flips every default-constructed engine (and the
    ``codec.*_device`` batch-of-one wrappers) onto the fused Pallas kernel
    path — how the ``kernels-interpret`` CI leg re-runs the whole
    engine/conformance/property surface against the kernels:

        FPTC_USE_KERNELS=1 pytest ...

    The kernel path is bit-identical to the XLA path by construction, so
    the toggle changes which device programs run — never bytes.
    """
    return os.environ.get("FPTC_USE_KERNELS", "").strip().lower() in (
        "1", "true", "yes", "on"
    )


def serving_devices(devices: DevicesArg = "auto") -> Tuple[Any, ...]:
    """Resolve a devices argument to the tuple the scheduler shards over.

    ``None`` — single-shard, default placement (arrays stay uncommitted;
    exactly the pre-sharding engine behavior).  ``"auto"`` — one shard per
    visible local device when there is more than one, else the single-shard
    default; shard 0 keeps *default* placement (None) so small/batch-of-one
    work stays uncommitted and honors ``jax.default_device`` instead of
    silently occupying device 0, while shards 1..n-1 commit to the
    remaining local devices.  An explicit sequence pins every shard to
    those devices (arrays are committed to them).
    """
    if devices is None:
        return (None,)
    if devices == "auto":
        local = jax.local_devices()
        return (None, *local[1:]) if len(local) > 1 else (None,)
    devs = tuple(devices)
    if not devs:
        raise ValueError("devices must be None, 'auto', or a non-empty "
                         "sequence of jax devices")
    return devs


def putter(device: Any) -> Callable[[Any], Any]:
    """The engines' one placement idiom: uncommitted default-device upload
    when ``device`` is None (the single-shard behavior), committed
    ``jax.device_put`` onto the shard's device otherwise."""
    if device is None:
        import jax.numpy as jnp

        return jnp.asarray
    return lambda x: jax.device_put(x, device)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One schedulable unit of engine work: the members of one key group
    assigned to one shard.  ``items`` are caller-side indices in input
    order; ``device`` is None for default placement (single-shard mode)."""

    key: Hashable
    shard: int
    device: Any
    items: Tuple[int, ...]


def member_positions(buckets: Sequence[Bucket], count: int) -> List[int]:
    """Per original index, its position in the buckets' flattened member
    order — what restores caller order after a bucket-ordered drain."""
    pos = [0] * count
    i = 0
    for b in buckets:
        for item in b.items:
            pos[item] = i
            i += 1
    return pos


class BucketScheduler:
    """Owns grouping, shard assignment and bucket rounding for the engines.

    Grouping preserves first-appearance key order with members in input
    order inside each group — the contract every engine (and the caller
    order restoration built on :func:`member_positions`) relies on.  With
    ``num_shards > 1`` each group's members additionally split into
    contiguous per-device shards, so one fused dispatch per (key, shard)
    runs on its own device and the per-shard results stay device-resident
    until the single drain.

    ``policy`` picks the bucket-edge ladder every traced axis rounds with
    (:meth:`round`): a :class:`~repro.tuning.policy.BucketPolicy`, a name
    (``"p2"`` / ``"half-octave"`` / ``"cost-balanced"``), or None for the
    ``FPTC_BUCKET_POLICY`` env default (``p2`` — the historical rounding).
    Policies trade padding waste against jit-specialization count and
    never change produced bytes.
    """

    def __init__(self, devices: DevicesArg = "auto",
                 policy: PolicyArg = None):
        self.devices = serving_devices(devices)
        self.policy = BucketPolicy.of(policy)

    def round(self, x: int) -> int:
        """Bucket-edge rounding for a traced axis under this scheduler's
        policy (the old hard-coded ``p2(x)`` when policy is ``p2``)."""
        return self.policy.round(max(int(x), 1))

    @property
    def num_shards(self) -> int:
        return len(self.devices)

    def device_of(self, shard: int) -> Any:
        return self.devices[shard]

    @staticmethod
    def group_by(keys: Sequence[Hashable]) -> Tuple[
        List[Hashable], Dict[Hashable, List[int]]
    ]:
        """Group indices by key: (first-appearance key order, key->indices
        in input order) — the one grouping loop all engines share."""
        order: List[Hashable] = []
        groups: "OrderedDict[Hashable, List[int]]" = OrderedDict()
        for i, key in enumerate(keys):
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append(i)
        return order, groups

    def buckets(
        self,
        keys: Sequence[Hashable],
        shard_ids: Optional[Sequence[int]] = None,
        shard_devices: Optional[Dict[int, Any]] = None,
        item_costs: Optional[Sequence[float]] = None,
    ) -> List[Bucket]:
        """Schedule items into (key, shard) buckets.

        Without ``shard_ids``, each key group's members split into
        ``min(len(group), num_shards)`` contiguous per-device shards
        placed on this scheduler's devices, with the starting shard
        rotating across groups — an archive of many small (domain,
        config) groups still spreads over every device instead of piling
        onto shard 0.  The split is equal-count unless ``item_costs``
        gives a predicted cost per item (one float per key, any units —
        e.g. :meth:`repro.tuning.cost_model.CostModel.signal_decode_cost`),
        in which case each group partitions contiguously at
        cost-balanced boundaries instead: mixed archives where one
        signal decodes 100x slower than another stop making every other
        device wait on the heavy shard.  Splits stay contiguous either
        way, so member order (and hence bytes) never changes.
        With ``shard_ids`` (one per item — a
        *pinning*, e.g. the transcode pipeline keeping a signal's
        re-encode on the device that decoded it), members partition by
        their given shard instead, ascending shard order, relative order
        preserved; ``shard_devices`` then maps those shard ids to devices
        (required whenever the pinned ids come from a different scheduler
        — the data's placement wins over this scheduler's own device
        tuple).
        """
        order, groups = self.group_by(keys)
        out: List[Bucket] = []
        next_shard = 0  # rotating start keeps small groups off shard 0
        for key in order:
            idxs = groups[key]
            if shard_ids is None:
                if item_costs is not None and self.num_shards > 1:
                    parts = _split_balanced(
                        idxs, [float(item_costs[i]) for i in idxs],
                        self.num_shards,
                    )
                else:
                    parts = _split_contiguous(idxs, self.num_shards)
                shards = [
                    (next_shard + j) % self.num_shards
                    for j in range(len(parts))
                ]
                next_shard = (next_shard + len(parts)) % self.num_shards
            else:
                by_shard: "OrderedDict[int, List[int]]" = OrderedDict()
                for i in idxs:
                    by_shard.setdefault(int(shard_ids[i]), []).append(i)
                shards = sorted(by_shard)
                parts = [by_shard[s] for s in shards]
            for shard, part in zip(shards, parts):
                if shard_devices is not None:
                    device = shard_devices[shard]
                elif shard < len(self.devices):
                    device = self.devices[shard]
                else:
                    raise ValueError(
                        f"pinned shard id {shard} has no device: this "
                        f"scheduler holds {self.num_shards} shard(s) — "
                        "pass shard_devices when shard_ids come from "
                        "another scheduler"
                    )
                out.append(Bucket(
                    key=key,
                    shard=shard,
                    device=device,
                    items=tuple(part),
                ))
        return out


def _split_contiguous(items: List[int], num_shards: int) -> List[List[int]]:
    k = min(len(items), max(num_shards, 1))
    if k <= 1:
        return [list(items)]
    q, r = divmod(len(items), k)
    out, off = [], 0
    for s in range(k):
        size = q + (1 if s < r else 0)
        out.append(items[off:off + size])
        off += size
    return out


def _split_balanced(
    items: List[int], costs: List[float], num_shards: int
) -> List[List[int]]:
    """Contiguous partition of ``items`` into <= ``num_shards`` parts with
    near-equal predicted cost: greedily close part ``s`` once its running
    cost reaches the ideal boundary ``total * (s+1) / k``.  Equal costs
    give the same +-1 size balance as the equal-count split (remainder
    items may land on different parts); contiguity keeps member (and
    byte) order identical to the unweighted path."""
    k = min(len(items), max(num_shards, 1))
    total = sum(costs)
    if k <= 1 or not (total > 0.0):
        return _split_contiguous(items, num_shards)
    out: List[List[int]] = []
    part: List[int] = []
    acc = 0.0
    s = 0
    for j, (item, cost) in enumerate(zip(items, costs)):
        part.append(item)
        acc += cost
        remaining_items = len(items) - (j + 1)
        remaining_parts = k - (s + 1)
        if remaining_parts <= 0:
            continue
        # close this part at its ideal cost boundary, or when the leftover
        # items are only just enough to make every remaining part non-empty
        if acc >= total * (s + 1) / k or remaining_items <= remaining_parts:
            out.append(part)
            part = []
            s += 1
    if part:
        out.append(part)
    return out


# ---------------------------------------------------------------------------
# The staging contract for device-resident encode staging.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class GatherStage:
    """Stage an encode bucket by gathering rows INSIDE the fused dispatch.

    ``flat`` is a flattened device tensor of decoded samples carrying
    enough trailing zeros that every ``dynamic_slice`` of the bucket width
    stays in bounds; row ``r`` of the bucket covers samples
    ``[starts[r], starts[r] + lens[r])`` and is exact-zero beyond
    ``lens[r]``.  ``donate`` marks the bucket as ``flat``'s last consumer,
    letting XLA reuse the buffer for the bucket's outputs (ignored on
    backends without donation support, e.g. CPU).
    """

    flat: Any  # f32[T + width] device array
    starts: Any  # int32[K]
    lens: Any  # int32[K]
    donate: bool = False


# ---------------------------------------------------------------------------
# The incremental submission surface shared by the engines.
# ---------------------------------------------------------------------------
class SubmitBuffer:
    """Thread-safe pending-work buffer behind the engines' ``submit`` /
    ``flush`` surface.

    The batch engines historically assumed batch-at-once staging: callers
    hand ``decode``/``encode``/``transcode`` a fully formed sequence.  A
    serving front-end forms batches *incrementally* — requests trickle in
    from admission threads, and the batch only exists when the
    micro-batcher decides to flush.  ``submit`` appends one work item (any
    thread) and returns its index in flush order; ``take`` atomically
    claims everything pending (the flushing thread's move).  The buffer
    carries items only — deadlines, shedding and queue bounds are the
    front-end's admission policy (:mod:`repro.serving.frontend`), not the
    engines'.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._items: List[Any] = []

    def submit(self, item: Any) -> int:
        """Append one pending item; returns its index in the next flush."""
        with self._lock:
            self._items.append(item)
            return len(self._items) - 1

    def take(self) -> List[Any]:
        """Atomically claim (and clear) everything pending, in order."""
        with self._lock:
            items, self._items = self._items, []
            return items

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


# ---------------------------------------------------------------------------
# The pipelined executor.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ExecutorStats:
    """Cumulative counters of one executor; callers read them as deltas."""

    buckets: int = 0
    pipelined_buckets: int = 0  # buckets whose upload ran on the worker
    upload_s: float = 0.0  # host staging + h2d time (worker or inline)


_IDLE_SPAN = contextlib.nullcontext()


def span(name: str, **stats: Any):
    """A host span of the serving path, live only while ``jax.profiler``
    is tracing.

    It opens a ``TraceAnnotation`` called ``name`` (``fptc.<part>``): an
    event on the trace's host plane, on the same clock as the device ops,
    so idle device time can be put down to the host work open at that
    moment.  A stat value may be a zero-argument callable, evaluated only
    while tracing.  With the profiler off the cost is one
    ``is_enabled()`` check: no annotation is made and no stat computed.
    Counters live in the ``*Stats`` dataclasses; no span is kept in memory.
    """
    if not TraceAnnotation.is_enabled():
        return _IDLE_SPAN
    return TraceAnnotation(
        name, **{k: v() if callable(v) else v for k, v in stats.items()})


class PipelineExecutor:
    """Runs bucket work as stage(upload) -> stage(dispatch), double-buffered.

    Work items are opaque to the executor (engines pass
    :class:`Bucket`\\ s, ``decode_streams`` passes its stream groups):
    ``upload(item)`` does the host staging and h2d transfer for one
    bucket; ``dispatch(item, staged)`` launches its fused device work.
    With ``pipeline=True`` and more than one bucket, a single staging
    worker keeps up to ``prefetch`` uploads in flight ahead of the main
    thread's dispatches — host staging and h2d upload of bucket k+1
    overlap device compute of bucket k (dispatch itself is async, so d2h
    drains issued later overlap the remaining dispatches too).  Dispatch
    order is always bucket order and every bucket sees exactly the same
    staged inputs, so the pipelined path is byte-identical to the serial
    one by construction.

    The worker thread performs transfers but never traces: jit tracing,
    plan-cache access and dispatch stay on the calling thread.
    """

    def __init__(self, *, pipeline: bool = True, prefetch: int = 2):
        if prefetch < 1:
            raise ValueError(f"prefetch must be >= 1, got {prefetch}")
        self.pipeline = pipeline
        self.prefetch = prefetch
        self.stats = ExecutorStats()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._lock = threading.Lock()

    def _worker(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="fptc-stage"
                )
        return self._pool

    def run(
        self,
        work: Sequence[Any],
        upload: Callable[[Any], Any],
        dispatch: Callable[[Any, Any], Any],
    ) -> List[Any]:
        n = len(work)
        self.stats.buckets += n
        if n == 0:
            return []

        def timed_upload(b: Any) -> Any:
            t0 = time.perf_counter()
            try:
                with span("fptc.stage"):
                    return upload(b)
            finally:
                self.stats.upload_s += time.perf_counter() - t0

        if not self.pipeline or n == 1:
            return [dispatch(b, timed_upload(b)) for b in work]

        pool = self._worker()
        results: List[Any] = [None] * n
        pending: "deque[Tuple[int, Any, Any]]" = deque()

        def pop_dispatch() -> None:
            j, bj, fut = pending.popleft()
            results[j] = dispatch(bj, fut.result())

        try:
            for i, b in enumerate(work):
                pending.append((i, b, pool.submit(timed_upload, b)))
                self.stats.pipelined_buckets += 1
                if len(pending) > self.prefetch:
                    pop_dispatch()
            while pending:
                pop_dispatch()
        finally:
            # on error, drain leftover staging futures so their (harmless)
            # transfers don't outlive the arrays they close over.
            # cancel() is a no-op on an already-RUNNING future — the
            # staging worker must be JOINED, not abandoned, or its
            # in-flight upload (possibly holding donated buffers) outlives
            # this call and the next run() races it on the 1-thread pool
            while pending:
                _, _, fut = pending.popleft()
                if not fut.cancel():
                    try:
                        fut.result()
                    except BaseException:
                        pass  # the primary exception is already in flight
        return results


def fetch_to_host(arrays: Sequence[Any]) -> List[np.ndarray]:
    """Drain device arrays: start EVERY d2h copy before materializing any.

    ``np.asarray`` per array serializes transfer-and-wait; issuing all
    ``copy_to_host_async`` first lets the copies overlap each other and any
    still-executing dispatches — the drain-side half of the double buffer.
    """
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()
    return [np.asarray(a) for a in arrays]


def fetch_to_host_stitched(
    bucket_arrays: Sequence[Sequence[Any]],
    stitch: Callable[[int, List[np.ndarray]], Any],
) -> List[Any]:
    """Drain per-bucket device arrays and overlap the host-side stitch.

    The drain-side double buffer, extended into the numpy post-processing:
    every bucket's d2h copies start up front (as :func:`fetch_to_host`),
    then the main thread materializes bucket ``k+1``'s arrays while a
    single worker runs ``stitch(k, host_arrays)`` — so the per-signal
    chunk-run concatenation of bucket ``k`` happens while bucket ``k+1``'s
    copies land, instead of serializing all transfers before the first
    stitch.  Results come back in bucket order; a stitch exception
    propagates to the caller (remaining stitches are abandoned with the
    pool).
    """
    for arrays in bucket_arrays:
        for a in arrays:
            start = getattr(a, "copy_to_host_async", None)
            if start is not None:
                start()
    if not bucket_arrays:
        return []
    if len(bucket_arrays) == 1:
        return [stitch(0, [np.asarray(a) for a in bucket_arrays[0]])]
    with ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="fptc-stitch"
    ) as pool:
        futures = []
        for b, arrays in enumerate(bucket_arrays):
            host = [np.asarray(a) for a in arrays]  # waits on bucket b only
            futures.append(pool.submit(stitch, b, host))
        return [f.result() for f in futures]
