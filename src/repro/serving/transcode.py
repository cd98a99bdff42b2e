"""Device-resident transcode pipeline: decode -> re-encode, no host round trip.

FPTC's asymmetric design puts batch *re-compression* on the server: archives
are routinely migrated between configs — tighter quantization for cold
storage, a new window size ``n`` or coefficient count ``e`` after a domain
recalibration.  Composing the two serving engines through host containers
pays one device->host drain per decoded signal, a host re-stack, and one
host->device re-upload per encode bucket, all in the middle of the hot loop.

:class:`Transcoder` removes the round trip by making the engines' internal
stream representations a shared, device-resident contract:

  * **Source streams.**  A host archive (``Container`` list) stages through
    the decoder's own lazy bucket staging (the executor overlaps each
    bucket's concat+upload with the previous bucket's decode); a
    device-resident :class:`~repro.serving.batch_encode.EncodedBatch` feeds
    its un-stitched chunk parts through ``core.symlen.stitch_chunk_parts``
    — a device-side gather that lays the per-chunk word runs into
    decoder-shaped concatenated bucket streams (capacity sized by the
    host-computable :func:`~repro.core.symlen.chunk_words_bound`, so no
    sync on the true word counts; opt-in ``exact_capacity=True`` trades
    ONE pre-decode sync on the true counts for ~2x less decode slot work
    on chunk-heavy sources).
  * **Decode.**  :meth:`BatchDecoder.decode_streams` — the same fused
    bucket dispatches ``decode()`` uses, minus the container unpacking.
  * **Re-stage on device, fused.**  Each target encode bucket's stacked
    signal matrix is a batched ``dynamic_slice`` gather out of the decoded
    window tensors that runs *inside* the bucket's fused encode dispatch
    (the :class:`~repro.serving.engine.GatherStage` staging contract — one
    jit per bucket, the flat source buffer donated on its last use); row
    layout, zero padding and chunk-size selection are the encoder's own
    (:meth:`BatchEncoder.encode_staged`), which is what makes the output
    **byte-identical** to draining the decoded signals to host and
    re-encoding them.
  * **Sharding.**  With several devices, a signal re-encodes on the shard
    that decoded it (``shard_ids`` pins the encode buckets), so the whole
    decode -> gather -> re-encode chain stays on one device per shard and
    the shards run embarrassingly parallel.
  * **One drain.**  The result is a normal :class:`EncodedBatch`; nothing
    syncs until its ``to_host()``.  Between decode and re-encode there are
    zero device->host transfers (the conformance suite pins this with a
    ``jax.transfer_guard``).

``core.codec.transcode`` is a container-of-one wrapper over this engine in
exact packing mode, mirroring ``encode_device`` / ``decode_device``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import symlen
from repro.core.calibration import DomainTables
from repro.core.container import Container
from repro.serving._plans import PlanCache, TranscodePlan
from repro.serving.batch_decode import (
    BatchDecoder,
    StreamGroup,
    _stage_container_group,
)
from repro.serving.batch_encode import (
    DEFAULT_CHUNK_SIZE,
    BatchEncoder,
    EncodedBatch,
)
from repro.serving.engine import (
    DevicesArg,
    GatherStage,
    SubmitBuffer,
    member_positions,
    putter,
)
from repro.tuning.policy import PolicyArg

__all__ = ["Transcoder", "TranscodePlan", "default_transcoder"]

TablesArg = Union[DomainTables, Dict[int, DomainTables]]
Source = Union[Sequence[Container], EncodedBatch]


def _signal_words_bound(
    num_symbols: int, chunk_size: int, l_max: int
) -> int:
    """Host-side bound on one signal's packed word count under chunking."""
    full, rem = divmod(int(num_symbols), int(chunk_size))
    return full * symlen.chunk_words_bound(chunk_size, l_max) + (
        symlen.chunk_words_bound(rem, l_max)
    )


@dataclasses.dataclass
class TranscoderStats:
    batches: int = 0
    signals: int = 0
    stitches: int = 0  # device-side chunk-part stitch dispatches
    capacity_syncs: int = 0  # exact_capacity pre-decode word-count syncs
    plan_hits: int = 0
    plan_misses: int = 0
    quarantined: int = 0  # signals poisoned out of quarantine=True batches


class Transcoder:
    """Re-encodes batches under a new (domain, config) without leaving the
    device.

    Usage::

        tc = Transcoder()                       # chunked (fast) packing
        batch = tc.transcode(containers, src_tables, dst_tables)
        migrated = batch.to_host()              # the ONLY host sync

    ``source`` is either a container archive (one upload, zero syncs) or a
    device-resident :class:`EncodedBatch` fresh off a
    :class:`BatchEncoder` — in which case its chunk parts are stitched
    into decoder streams on device and the batch is *consumed* (a later
    ``to_host()`` on it raises; drain the transcode result instead).
    Output signal order is source order.  ``dst_domain_ids`` routes each
    signal's target tables when ``dst_tables`` is a mapping; it defaults
    to the source domain ids (re-windowing / re-quantizing within the
    same domain id).  ``pipeline``/``devices`` are the shared engine-layer
    knobs; ``exact_capacity=True`` opts into one pre-decode sync on the
    true stitched word counts (EncodedBatch sources only) to shrink
    decode slot work for chunk-heavy streams — none of them change the
    produced bytes.
    """

    def __init__(
        self,
        *,
        chunk_size: Optional[int] = DEFAULT_CHUNK_SIZE,
        use_kernels: Optional[bool] = None,
        decoder: Optional[BatchDecoder] = None,
        encoder: Optional[BatchEncoder] = None,
        plan_cache_size: int = 32,
        pipeline: bool = True,
        devices: DevicesArg = "auto",
        prefetch: int = 2,
        exact_capacity: bool = False,
        policy: PolicyArg = None,
    ):
        # use_kernels threads through BOTH stage definitions: the decode
        # megakernel and the fused encode tile (None: the decoder picks its
        # arm per bucket, the encoder takes the FPTC_USE_KERNELS default;
        # bytes are identical either way)
        self.decoder = decoder or BatchDecoder(
            use_kernels=use_kernels, pipeline=pipeline, devices=devices,
            prefetch=prefetch, policy=policy,
        )
        self.encoder = encoder or BatchEncoder(
            chunk_size=chunk_size, use_kernels=use_kernels,
            pipeline=pipeline, devices=devices, prefetch=prefetch,
            policy=policy,
        )
        if self.decoder.scheduler.devices != self.encoder.scheduler.devices:
            raise ValueError(
                "decoder and encoder must shard over the same devices — a "
                "signal re-encodes on the shard that decoded it (got "
                f"{self.decoder.scheduler.devices} vs "
                f"{self.encoder.scheduler.devices})"
            )
        if self.decoder.scheduler.policy != self.encoder.scheduler.policy:
            # max_width (and the flat gather pad) are sized by the ENCODE
            # bucket ladder; mixing ladders across the two halves is legal
            # arithmetic but a silent perf/compile-count trap — refuse
            raise ValueError(
                "decoder and encoder must use the same bucket policy (got "
                f"{self.decoder.scheduler.policy.name!r} vs "
                f"{self.encoder.scheduler.policy.name!r})"
            )
        self.exact_capacity = exact_capacity
        self._plans = PlanCache(self._build_plan, plan_cache_size)
        self.stats = TranscoderStats()
        self._pending = SubmitBuffer()

    # -- incremental submission (the front-end's surface) -------------------
    def submit(
        self, container: Container, dst_domain_id: Optional[int] = None
    ) -> int:
        """Queue one container for the next :meth:`flush` (thread-safe).

        The incremental half of the batch-at-once :meth:`transcode` — see
        :meth:`~repro.serving.batch_decode.BatchDecoder.submit`.
        ``dst_domain_id`` routes the re-encode tables when the flush passes
        a mapping (None = keep the source domain id).
        """
        return self._pending.submit((container, dst_domain_id))

    @property
    def pending(self) -> int:
        """Containers submitted since the last flush."""
        return len(self._pending)

    def flush(
        self,
        src_tables: TablesArg,
        dst_tables: TablesArg,
        *,
        quarantine: bool = False,
    ) -> EncodedBatch:
        """Transcode everything submitted since the last flush as one batch
        (submission order).  An empty flush is a no-op empty batch."""
        items = self._pending.take()
        containers = [c for c, _ in items]
        if all(d is None for _, d in items):
            dst_ids = None  # transcode()'s own per-tables-type defaulting
        else:
            # fill unrouted members exactly like transcode()'s None default
            # would: the single tables' own id, or the source domain id
            # under a mapping
            single = (
                dst_tables if isinstance(dst_tables, DomainTables) else None
            )

            def _src_domain(c) -> int:
                if isinstance(c, Container):
                    return c.domain_id
                try:  # quarantine admits raw bytes; route off the header
                    return Container.peek(c).domain_id
                except Exception:
                    return 0  # unparseable: poisoned before routing matters

            dst_ids = [
                d if d is not None
                else (single.domain_id if single is not None
                      else _src_domain(c))
                for c, d in items
            ]
        return self.transcode(
            containers, src_tables, dst_tables, dst_domain_ids=dst_ids,
            quarantine=quarantine,
        )

    @property
    def scheduler(self):
        """The shard scheduler both halves of the pipeline follow."""
        return self.decoder.scheduler

    # -- plan pairing ------------------------------------------------------
    def _build_plan(self, tables, key, device) -> TranscodePlan:
        (src_tab, dst_tab), (src_key, dst_key) = tables, key
        return TranscodePlan(
            decode=self.decoder._plans.get(src_tab, src_key, device),
            encode=self.encoder.plan_for(dst_tab, device),
            src_key=src_key,
            dst_key=dst_key,
        )

    def plan_for(
        self, src_tables: DomainTables, dst_tables: DomainTables, device=None
    ) -> TranscodePlan:
        src_cfg, dst_cfg = src_tables.config, dst_tables.config
        src_key = (
            src_tables.domain_id, src_cfg.n, src_cfg.e, src_cfg.l_max,
            src_cfg.coding,
        )
        dst_key = (
            dst_tables.domain_id, dst_cfg.n, dst_cfg.e, dst_cfg.l_max,
            dst_cfg.coding,
        )
        return self._plans.get(
            (src_tables, dst_tables), (src_key, dst_key), device
        )

    # -- source normalization ----------------------------------------------
    def _streams_from_encoded(
        self, batch: EncodedBatch, src_tables: TablesArg
    ) -> Tuple[List[StreamGroup], List[int], List[Tuple[int, int]],
               List[tuple], List[int]]:
        """Stitch an EncodedBatch's chunk parts into decoder streams,
        entirely on device (each shard's parts stitch on their own
        device).  Returns (groups, per-signal member position, per-signal
        (length, src plan key) in source order, pending gap flags,
        per-signal shard ids).  Does NOT consume the batch — transcode()
        marks it consumed only once the whole pipeline is committed, so a
        failed transcode (bad routing, missing tables) leaves the source
        drainable."""
        parts = batch.device_parts()
        for p in parts:
            key = tuple(p.plan_key)
            if len(key) == 5 and tuple(key[4]) != (0, 0, False):
                # a v3-coded SOURCE stream needs its per-signal ncoded /
                # zero-plane bitmaps on host to build the decode expansion
                # (symlen.v3_expand_index) — a sync this zero-transfer path
                # refuses by contract.  Drain the batch and feed the host
                # containers instead (the container path decodes v3 fine);
                # v2 -> v3 *upgrades* (v3 on the TARGET) are unaffected.
                raise NotImplementedError(
                    "device-resident transcode from a v3-coded EncodedBatch "
                    f"source (coding={tuple(key[4])}) is not supported — "
                    "drain it with to_host() and transcode the containers, "
                    "or keep the source coding trivial"
                )
        slices = batch.signal_slices()
        # signals per bucket, in row order (== stream symbol order)
        per_bucket: List[List] = [[] for _ in parts]
        for s in slices:
            per_bucket[s.bucket].append(s)
        for rows in per_bucket:
            rows.sort(key=lambda s: s.row)

        # merge source buckets sharing (plan_key, shard) into one decode
        # group, mirroring the container path's grouping — same
        # fused-dispatch count and window bucket as the drained-container
        # round trip, with every shard's stream staying on its device
        key_order, by_key = self.scheduler.group_by(
            [(p.plan_key, p.shard) for p in parts]
        )

        # exact_capacity: ONE batched pre-decode sync on the true per-chunk
        # word counts, so the stitched streams are sized by what was packed
        # instead of the l_max worst case (~2-3x looser); decode work is
        # linear in capacity, bytes are identical either way
        wpc_host = None
        if self.exact_capacity:
            wpc_host = jax.device_get([p.words_per_chunk for p in parts])
            self.stats.capacity_syncs += 1

        groups: List[StreamGroup] = []
        member_pos_by_sig: Dict[Tuple[int, int], int] = {}
        pos = 0
        for key, shard in key_order:
            l_max = key[3]
            seg_hi, seg_lo, seg_sl = [], [], []
            members: List[Tuple[int, int]] = []
            tab = self.decoder._tables_for(key, src_tables)
            lengths = np.asarray(tab.book.lengths)
            nonzero = lengths[lengths > 0]
            min_len = int(nonzero.min()) if nonzero.size else 1
            max_sl = min(symlen.WORD_BITS // max(min_len, 1),
                         symlen.WORD_BITS)
            device = None
            for b in by_key[(key, shard)]:
                p = parts[b]
                device = p.device
                if wpc_host is not None:
                    cap = int(np.sum(wpc_host[b]))
                else:
                    cap = sum(
                        _signal_words_bound(
                            s.num_windows * s.e, p.chunk_size, l_max
                        )
                        for s in per_bucket[b]
                    )
                c = p.chunk_size
                shi, slo, ssl, _ = symlen.stitch_chunk_parts(
                    p.hi.reshape(-1, c),
                    p.lo.reshape(-1, c),
                    p.symlen.reshape(-1, c),
                    p.words_per_chunk.reshape(-1),
                    capacity=symlen.stitch_capacity(cap),
                )
                self.stats.stitches += 1
                seg_hi.append(shi)
                seg_lo.append(slo)
                seg_sl.append(ssl)
                for s in per_bucket[b]:
                    members.append((s.num_windows, s.signal_length))
                    member_pos_by_sig[(s.bucket, s.row)] = pos
                    pos += 1
            groups.append(StreamGroup(
                plan_key=key,
                hi=seg_hi[0] if len(seg_hi) == 1 else jnp.concatenate(seg_hi),
                lo=seg_lo[0] if len(seg_lo) == 1 else jnp.concatenate(seg_lo),
                symlen=(
                    seg_sl[0] if len(seg_sl) == 1 else jnp.concatenate(seg_sl)
                ),
                max_symlen=max_sl,
                members=members,
                device=device,
                shard=shard,
            ))

        member_pos = [
            member_pos_by_sig[(s.bucket, s.row)] for s in slices
        ]
        meta = [
            (s.signal_length, (s.domain_id, s.n, s.e, s.l_max))
            for s in slices
        ]
        shard_ids = [parts[s.bucket].shard for s in slices]
        # inherit the source's own pending flags too: a chained transcode
        # must not launder an upstream histogram-gap batch into a clean
        # drain
        flags = list(batch._pending_flags) + [
            (p.plan_key, p.unencodable) for p in parts
        ]
        return groups, member_pos, meta, flags, shard_ids

    # -- the transcode -----------------------------------------------------
    def transcode(
        self,
        source: Source,
        src_tables: TablesArg,
        dst_tables: TablesArg,
        *,
        dst_domain_ids: Optional[Sequence[int]] = None,
        quarantine: bool = False,
    ) -> EncodedBatch:
        """Decode ``source`` under ``src_tables`` and re-encode under
        ``dst_tables``, device-resident end to end.

        Returns an :class:`EncodedBatch` (source order); nothing is synced
        to host here — drain it once with ``to_host()``.

        ``quarantine=True`` (container sources): items may be raw bytes or
        :class:`Container` objects; each is validated against
        ``src_tables`` at staging and a poisoned item is excluded from its
        bucket instead of raising batch-wide — its typed error rides the
        returned batch's drain.  EncodedBatch sources are device-resident
        output of our own engines (no wire format to corrupt), so only the
        per-signal histogram-gap demotion applies to them.
        """
        src_batch: Optional[EncodedBatch] = None
        poisoned: Dict[int, Exception] = {}
        clean_pos: List[int] = []
        total = 0
        if isinstance(source, EncodedBatch):
            src_batch = source
            groups, member_pos, meta, flags, shard_ids = (
                self._streams_from_encoded(source, src_tables)
            )
            # placement follows the DATA: the source batch's shard ids may
            # come from a different scheduler (e.g. a sharded encoder
            # feeding a single-device transcoder), so its parts' devices —
            # not this scheduler's tuple — decide where each shard runs
            shard_devices = {g.shard: g.device for g in groups}
        else:
            containers = list(source)
            total = len(containers)
            clean_pos = list(range(total))
            if quarantine:
                from repro.serving.quarantine import validate_or_poison

                clean_pos, clean = [], []
                for i, item in enumerate(containers):
                    c, err = validate_or_poison(item, i, src_tables)
                    if err is not None:
                        poisoned[i] = err
                    else:
                        clean_pos.append(i)
                        clean.append(c)
                self.stats.quarantined += len(poisoned)
                containers = clean
                if dst_domain_ids is not None:
                    dst_domain_ids = [dst_domain_ids[i] for i in clean_pos]
                if not containers:
                    self.stats.batches += 1
                    return EncodedBatch(
                        [], [None] * total, (),
                        poisoned=poisoned, quarantine=True,
                    )
            buckets = self.scheduler.buckets(
                [c.plan_key for c in containers]
            )
            member_pos = member_positions(buckets, len(containers))
            # lazy staging: the decode executor's worker concatenates and
            # uploads bucket k+1 while bucket k decodes
            groups = [
                functools.partial(
                    _stage_container_group,
                    [containers[i] for i in b.items],
                    b.key, b.device, b.shard,
                    self.decoder.scheduler.round,
                )
                for b in buckets
            ]
            meta = [(c.signal_length, c.plan_key) for c in containers]
            flags = []
            shard_ids = [0] * len(containers)
            shard_devices = {}
            for b in buckets:
                shard_devices[b.shard] = b.device
                for i in b.items:
                    shard_ids[i] = b.shard
        self.stats.batches += 1
        self.stats.signals += len(meta)

        lengths = [length for length, _ in meta]
        if dst_domain_ids is None and not isinstance(
            dst_tables, DomainTables
        ):
            dst_domain_ids = [key[0] for _, key in meta]

        # resolve the (source, target) plan pairings up front: device
        # tables/bases upload through the shared caches before dispatch.
        # max_width (the widest dst encode bucket) sizes the one-time zero
        # pad that keeps every fused gather's dynamic_slice in bounds.
        dst_doms = (
            [dst_tables.domain_id] * len(meta)
            if isinstance(dst_tables, DomainTables) else list(dst_domain_ids)
        )
        max_width = 1
        for (length, src_key), dst_dom, shard in zip(
            meta, dst_doms, shard_ids
        ):
            src_tab = self.decoder._tables_for(src_key, src_tables)
            dst_tab = self.encoder._tables_for(dst_dom, dst_tables)
            self.plan_for(src_tab, dst_tab, shard_devices[shard])
            n_dst = dst_tab.config.n
            # the ENCODER's bucket rounding, exactly: the fused gathers
            # dynamic_slice `wp * n` samples per row, and dynamic_slice
            # CLAMPS out-of-range starts — an undersized pad would silently
            # shift tail rows' windows instead of erroring
            max_width = max(
                max_width,
                self.encoder.scheduler.round(
                    max(-(-length // n_dst), 1)
                ) * n_dst,
            )
        self.stats.plan_hits = self._plans.hits
        self.stats.plan_misses = self._plans.misses

        decoded = self.decoder.decode_streams(groups, src_tables)
        group_shards = [
            g.shard if isinstance(g, StreamGroup) else None for g in groups
        ]
        if None in group_shards:
            # lazy container staging: shard rides the scheduler buckets
            group_shards = [b.shard for b in buckets]

        # join each shard's flat decoded sample tensors once (zero-padded by
        # the widest bucket so every gather slice stays in bounds, then up
        # to a bucket-edge length: the flat tensor is an operand of the
        # fused gather+encode jit, so an unbucketed data-dependent length
        # would recompile the whole DCT+quant+pack per distinct archive
        # size — policy rounding keeps those specializations O(density *
        # log sizes) like every other traced shape in the engines);
        # per-signal sample runs are contiguous, so encode staging is one
        # batched dynamic_slice fused into each bucket's encode dispatch
        tensors = decoded.device_samples
        starts = np.zeros((len(meta),), dtype=np.int64)
        flats: Dict[int, jnp.ndarray] = {}
        remaining: Dict[int, int] = {}
        if tensors:
            bases = np.zeros((len(tensors),), dtype=np.int64)
            for shard in sorted(set(group_shards)):
                gidx = [g for g, s in enumerate(group_shards) if s == shard]
                off = 0
                for g in gidx:
                    bases[g] = off
                    off += tensors[g].size
                if off + max_width > np.iinfo(np.int32).max:
                    # gather starts ride int32 (jax default x32): a flat
                    # tensor past 2^31 samples would wrap offsets negative
                    # and re-encode the wrong samples SILENTLY — refuse
                    raise ValueError(
                        f"shard {shard}'s decoded windows span "
                        f"{off + max_width} samples, past the int32 gather "
                        "range — transcode the archive in smaller batches"
                    )
                pad = putter(shard_devices[shard])(np.zeros(
                    (self.scheduler.round(off + max_width) - off,),
                    np.float32,
                ))
                flats[shard] = jnp.concatenate(
                    [tensors[g] for g in gidx] + [pad]
                )
                remaining[shard] = 0
            for i in range(len(meta)):
                s = decoded._slices[member_pos[i]]
                starts[i] = bases[s.group] + s.start
                remaining[shard_ids[i]] += 1

        def stage(idxs, kp: int, wp: int, n: int, device) -> GatherStage:
            shard = shard_ids[idxs[0]]  # bucket rows share one shard (pinned)
            st = np.zeros((kp,), dtype=np.int32)
            ln = np.zeros((kp,), dtype=np.int32)
            for row, i in enumerate(idxs):
                st[row] = starts[i]
                ln[row] = lengths[i]
            put = putter(device)
            remaining[shard] -= len(idxs)
            return GatherStage(
                flat=flats[shard],
                starts=put(st),
                lens=put(ln),
                # last bucket gathering from this shard's decoded windows:
                # donate the flat buffer into the fused encode
                donate=remaining[shard] == 0,
            )

        out = self.encoder.encode_staged(
            lengths, dst_tables,
            domain_ids=dst_domain_ids,
            stage=stage,
            pending_flags=flags,
            shard_ids=shard_ids,
            shard_devices=shard_devices,
            quarantine=quarantine,
        )
        if quarantine and src_batch is None and total:
            # restore source positions: poisoned slots hold their typed
            # error, clean slots keep their (unchanged) bucket/row slices
            full = [None] * total
            for j, i in enumerate(clean_pos):
                full[i] = out._slices[j]
            out = EncodedBatch(
                out._buckets, full, out._pending_flags,
                poisoned=poisoned, quarantine=True,
            )
        if src_batch is not None:
            # commit point: the source's buffers now back the transcode
            # result; mark it consumed only NOW, so any earlier failure
            # (bad routing, missing tables) left it drainable
            src_batch._mark_consumed(
                "its device buffers were donated to a Transcoder — drain "
                "the transcode result instead"
            )
        return out

    def transcode_to_host(
        self,
        source: Source,
        src_tables: TablesArg,
        dst_tables: TablesArg,
        *,
        dst_domain_ids: Optional[Sequence[int]] = None,
    ) -> List[Container]:
        """Convenience: transcode + single drain in one call."""
        return self.transcode(
            source, src_tables, dst_tables, dst_domain_ids=dst_domain_ids
        ).to_host()


# ---------------------------------------------------------------------------
# Process-wide default transcoders (codec.transcode rides the exact one).
# ---------------------------------------------------------------------------
_DEFAULTS: Dict[Optional[int], Transcoder] = {}


def default_transcoder(chunk_size: Optional[int] = None) -> Transcoder:
    """Shared transcoder per chunk size.  ``None`` (the default) is *exact*
    packing mode — what ``core.codec.transcode`` rides; pass
    ``DEFAULT_CHUNK_SIZE`` (or any chunk) for chunk-parallel packing.
    Same process-lifetime plan-cache trade as ``default_encoder``."""
    tc = _DEFAULTS.get(chunk_size)
    if tc is None:
        tc = _DEFAULTS[chunk_size] = Transcoder(chunk_size=chunk_size)
    return tc
