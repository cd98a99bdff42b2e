"""Shared serving-engine layer (tentpole coverage): scheduler grouping +
shard assignment, the double-buffered PipelineExecutor, and the
cross-engine guarantees the refactor rests on — pipelining, sharding,
bucket policies and kernel block tuning change *when/where* buckets run,
never the produced bytes, and add no device->host syncs before the single
drain."""
import threading
from collections import defaultdict

import jax
import numpy as np
import pytest

import repro.serving.batch_decode as batch_decode_mod
import repro.serving.batch_encode as batch_encode_mod
from repro.core import DOMAIN_DEFAULTS, calibrate, encode
from repro.data import make_signal
from repro.serving import (
    BatchDecoder,
    BatchEncoder,
    BucketScheduler,
    PipelineExecutor,
    Transcoder,
    serving_devices,
)
from repro.serving.engine import _split_balanced, member_positions
from repro.tuning.policy import POLICY_NAMES


# ---------------------------------------------------------------------------
# Scheduler units.
# ---------------------------------------------------------------------------
def test_group_by_first_appearance_order():
    order, groups = BucketScheduler.group_by(["b", "a", "b", "c", "a"])
    assert order == ["b", "a", "c"]
    assert groups == {"b": [0, 2], "a": [1, 4], "c": [3]}


def test_buckets_single_shard_matches_grouping():
    sched = BucketScheduler(devices=None)
    buckets = sched.buckets(["x", "y", "x", "x"])
    assert [(b.key, list(b.items)) for b in buckets] == [
        ("x", [0, 2, 3]), ("y", [1])
    ]
    assert all(b.shard == 0 and b.device is None for b in buckets)
    assert member_positions(buckets, 4) == [0, 3, 1, 2]


def test_buckets_contiguous_balanced_shards():
    # fake "devices": scheduling never touches them unless work dispatches
    sched = BucketScheduler(devices=["d0", "d1"])
    assert sched.num_shards == 2
    buckets = sched.buckets(["x"] * 5 + ["y"])
    assert [(b.key, b.shard, list(b.items)) for b in buckets] == [
        ("x", 0, [0, 1, 2]), ("x", 1, [3, 4]), ("y", 0, [5])
    ]
    assert buckets[1].device == "d1"
    # flattened member order is still group-major, members in input order
    assert member_positions(buckets, 6) == [0, 1, 2, 3, 4, 5]


def test_buckets_rotate_start_shard_across_groups():
    """Many small groups spread over every device: the starting shard
    rotates, instead of every single-member group landing on shard 0."""
    sched = BucketScheduler(devices=["d0", "d1", "d2", "d3"])
    buckets = sched.buckets(["a", "b", "c", "d", "e"])
    assert [b.shard for b in buckets] == [0, 1, 2, 3, 0]


def test_buckets_pinned_shard_ids():
    sched = BucketScheduler(devices=["d0", "d1", "d2"])
    buckets = sched.buckets(
        ["x", "x", "x", "y"], shard_ids=[2, 0, 2, 1]
    )
    assert [(b.key, b.shard, list(b.items)) for b in buckets] == [
        ("x", 0, [1]), ("x", 2, [0, 2]), ("y", 1, [3])
    ]


def test_scheduler_round_follows_policy(monkeypatch):
    # pin the env so the default-policy assertion holds under the CI
    # tuning leg (which exports FPTC_BUCKET_POLICY=cost-balanced)
    monkeypatch.delenv("FPTC_BUCKET_POLICY", raising=False)
    assert BucketScheduler(devices=None).round(5) == 8  # p2 default
    assert BucketScheduler(devices=None, policy="half-octave").round(5) == 6
    assert BucketScheduler(devices=None, policy="cost-balanced").round(5) == 5
    sched = BucketScheduler(devices=None, policy="half-octave")
    for x in (1, 2, 3, 7, 100, 1000):
        r = sched.round(x)
        assert r >= x
        assert sched.round(r) == r  # idempotent on edges


def test_split_balanced_equal_costs_stay_balanced():
    parts = _split_balanced(list(range(10)), [1.0] * 10, 4)
    assert sum(parts, []) == list(range(10))  # contiguous, order kept
    sizes = sorted(len(p) for p in parts)
    assert len(parts) == 4 and sizes[-1] - sizes[0] <= 1


def test_split_balanced_isolates_heavy_item():
    # one item worth more than everything else combined gets its own shard
    parts = _split_balanced([0, 1, 2, 3], [100.0, 1.0, 1.0, 1.0], 2)
    assert parts == [[0], [1, 2, 3]]


def test_split_balanced_degenerate_falls_back():
    from repro.serving.engine import _split_contiguous

    assert _split_balanced([0, 1], [1.0, 1.0], 1) == (
        _split_contiguous([0, 1], 1)
    )
    assert _split_balanced([0, 1], [0.0, 0.0], 2) == (
        _split_contiguous([0, 1], 2)
    )


def test_buckets_cost_balanced_shard_split():
    sched = BucketScheduler(devices=["d0", "d1"])
    buckets = sched.buckets(
        ["x", "x", "x", "x"], item_costs=[100.0, 1.0, 1.0, 1.0]
    )
    assert [(b.shard, list(b.items)) for b in buckets] == [
        (0, [0]), (1, [1, 2, 3])
    ]


def test_serving_devices_resolution():
    assert serving_devices(None) == (None,)
    local = jax.local_devices()
    auto = serving_devices("auto")
    # shard 0 keeps default (uncommitted) placement so batch-of-one work
    # through the default engines honors jax.default_device
    assert auto == ((None, *local[1:]) if len(local) > 1 else (None,))
    assert serving_devices(local) == tuple(local)
    with pytest.raises(ValueError, match="non-empty"):
        serving_devices([])


# ---------------------------------------------------------------------------
# Executor units.
# ---------------------------------------------------------------------------
def _work(n):
    sched = BucketScheduler(devices=None)
    return sched.buckets(list(range(n)))


@pytest.mark.parametrize("pipeline", [False, True])
def test_executor_results_in_bucket_order(pipeline):
    ex = PipelineExecutor(pipeline=pipeline)
    out = ex.run(
        _work(7),
        upload=lambda b: b.key * 10,
        dispatch=lambda b, staged: staged + 1,
    )
    assert out == [k * 10 + 1 for k in range(7)]
    assert ex.stats.buckets == 7


def test_executor_uploads_run_on_worker_and_dispatch_on_caller():
    ex = PipelineExecutor(pipeline=True, prefetch=2)
    upload_threads, dispatch_threads = set(), set()

    def upload(b):
        upload_threads.add(threading.current_thread().name)
        return b.key

    def dispatch(b, staged):
        dispatch_threads.add(threading.current_thread().name)
        return staged

    ex.run(_work(5), upload, dispatch)
    main = threading.current_thread().name
    assert dispatch_threads == {main}
    assert upload_threads and main not in upload_threads
    assert ex.stats.pipelined_buckets == 5


def test_executor_prefetch_bound():
    """The staging worker never runs more than `prefetch` buckets ahead of
    the last dispatched bucket."""
    ex = PipelineExecutor(pipeline=True, prefetch=2)
    state = {"uploaded": 0, "dispatched": 0}
    max_ahead = []

    def upload(b):
        state["uploaded"] += 1
        max_ahead.append(state["uploaded"] - state["dispatched"])
        return b.key

    def dispatch(b, staged):
        state["dispatched"] += 1
        return staged

    ex.run(_work(10), upload, dispatch)
    # upload k+prefetch may start only once bucket k dispatched (+1 for the
    # bucket currently between upload and dispatch)
    assert max(max_ahead) <= ex.prefetch + 1


def test_executor_single_bucket_stays_serial():
    ex = PipelineExecutor(pipeline=True)
    names = []
    ex.run(
        _work(1),
        upload=lambda b: names.append(threading.current_thread().name),
        dispatch=lambda b, staged: None,
    )
    assert names == [threading.current_thread().name]
    assert ex.stats.pipelined_buckets == 0


def test_executor_propagates_errors():
    ex = PipelineExecutor(pipeline=True)

    def upload(b):
        if b.key == 2:
            raise RuntimeError("stage boom")
        return b.key

    with pytest.raises(RuntimeError, match="stage boom"):
        ex.run(_work(4), upload, lambda b, s: s)
    # the executor stays usable after a failed run
    assert ex.run(_work(2), lambda b: b.key, lambda b, s: s) == [0, 1]


def test_executor_teardown_joins_inflight_upload():
    """Regression: a dispatch exception used to tear down via
    ``fut.cancel()`` alone — a no-op on an already-RUNNING future — so
    the staging worker's in-flight upload (possibly holding donated
    buffers) outlived run() and raced the next run() on the one-thread
    pool.  Teardown must JOIN the running upload before re-raising."""
    import time

    ex = PipelineExecutor(pipeline=True, prefetch=2)
    upload_started = threading.Event()
    uploads_done = []
    running = []  # uploads entered and not yet returned

    def upload(b):
        running.append(b.key)
        if b.key == 1:
            upload_started.set()
            time.sleep(0.3)  # long enough to be RUNNING at teardown
        uploads_done.append(b.key)
        running.remove(b.key)
        return b.key

    def dispatch(b, staged):
        # fail bucket 0's dispatch only once bucket 1's upload is
        # mid-flight on the staging worker
        assert upload_started.wait(10)
        raise RuntimeError("dispatch boom")

    with pytest.raises(RuntimeError, match="dispatch boom"):
        ex.run(_work(4), upload, dispatch)
    # the in-flight upload was joined (completed), not abandoned
    assert 1 in uploads_done
    # no upload is still running: nothing leaked into the next run
    assert running == []
    assert ex.run(_work(2), upload, lambda b, s: s) == [0, 1]
    assert running == []


def test_executor_rejects_bad_prefetch():
    with pytest.raises(ValueError, match="prefetch"):
        PipelineExecutor(prefetch=0)


# ---------------------------------------------------------------------------
# Cross-engine byte identity: pipelined / sharded == synchronous.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tables():
    power = calibrate(
        make_signal("load_power", 65536, seed=7),
        DOMAIN_DEFAULTS["power"],
        domain_id=0,
    )
    meteo = calibrate(
        make_signal("temperature", 65536, seed=8),
        DOMAIN_DEFAULTS["meteorological"],
        domain_id=1,
    )
    return {0: power, 1: meteo}


@pytest.fixture(scope="module")
def archive(tables):
    sigs, doms = [], []
    for i, n in enumerate([2048, 1000, 3000, 257 * 8, 700, 4096]):
        dom = i % 2
        ds = "load_power" if dom == 0 else "temperature"
        sigs.append(make_signal(ds, n, seed=90 + i))
        doms.append(dom)
    containers = [
        encode(s, tables[d]) for s, d in zip(sigs, doms)
    ]
    return sigs, doms, containers


def _container_bytes(containers):
    return [c.to_bytes() for c in containers]


def test_pipelined_decode_byte_identical(tables, archive):
    _, _, containers = archive
    sync = BatchDecoder(pipeline=False, devices=None)
    pipe = BatchDecoder(pipeline=True, devices=None, prefetch=3)
    ref = sync.decode(containers, tables).to_host()
    got = pipe.decode(containers, tables).to_host()
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    assert pipe.executor.stats.pipelined_buckets >= 1


def test_pipelined_encode_byte_identical(tables, archive):
    sigs, doms, _ = archive
    sync = BatchEncoder(pipeline=False, devices=None, chunk_size=64)
    pipe = BatchEncoder(pipeline=True, devices=None, chunk_size=64)
    ref = sync.encode(sigs, tables, domain_ids=doms).to_host()
    got = pipe.encode(sigs, tables, domain_ids=doms).to_host()
    assert _container_bytes(got) == _container_bytes(ref)


def test_pipelined_transcode_byte_identical(tables, archive):
    _, _, containers = archive
    sync = Transcoder(pipeline=False, devices=None)
    pipe = Transcoder(pipeline=True, devices=None)
    ref = sync.transcode_to_host(containers, tables, tables[1],
                                 dst_domain_ids=[1] * len(containers))
    got = pipe.transcode_to_host(containers, tables, tables[1],
                                 dst_domain_ids=[1] * len(containers))
    assert _container_bytes(got) == _container_bytes(ref)


def test_sharded_engines_byte_identical(tables, archive):
    """Explicitly sharding over every visible device produces the same
    bytes as the single-device path (the real multi-shard split runs under
    the multi-device CI leg; with one device this pins the committed-
    placement path)."""
    sigs, doms, containers = archive
    devs = jax.local_devices()

    ref = BatchDecoder(devices=None).decode(containers, tables).to_host()
    got = BatchDecoder(devices=devs).decode(containers, tables).to_host()
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)

    ref = BatchEncoder(devices=None, chunk_size=128).encode(
        sigs, tables, domain_ids=doms
    ).to_host()
    enc = BatchEncoder(devices=devs, chunk_size=128)
    got = enc.encode(sigs, tables, domain_ids=doms).to_host()
    assert _container_bytes(got) == _container_bytes(ref)
    if len(devs) > 1:
        assert enc.stats.dispatches >= 2  # the batch axis actually split

    ref = Transcoder(devices=None).transcode_to_host(
        containers, tables, tables[0], dst_domain_ids=[0] * len(containers)
    )
    got = Transcoder(devices=devs).transcode_to_host(
        containers, tables, tables[0], dst_domain_ids=[0] * len(containers)
    )
    assert _container_bytes(got) == _container_bytes(ref)


def test_sharded_encoded_batch_transcode_byte_identical(tables, archive):
    """EncodedBatch-source transcode under explicit sharding: each shard's
    chunk parts stitch and re-encode on their own device, byte-identical
    to the single-device pipeline."""
    sigs, doms, _ = archive
    devs = jax.local_devices()

    def run(devices):
        batch = BatchEncoder(devices=devices, chunk_size=64).encode(
            sigs, tables, domain_ids=doms
        )
        return Transcoder(devices=devices, chunk_size=64).transcode_to_host(
            batch, tables, tables[1], dst_domain_ids=[1] * len(sigs)
        )

    assert _container_bytes(run(devs)) == _container_bytes(run(None))


def test_exact_capacity_transcode_byte_identical(tables, archive):
    """exact_capacity=True (one pre-decode sync on the true stitched word
    counts) changes decode-slot work only — output bytes are identical."""
    sigs, doms, _ = archive
    src_batch = BatchEncoder(chunk_size=32).encode(
        sigs, tables, domain_ids=doms
    )
    tc = Transcoder(chunk_size=32, exact_capacity=True)
    got = tc.transcode_to_host(
        src_batch, tables, tables[0], dst_domain_ids=[0] * len(sigs)
    )
    assert tc.stats.capacity_syncs == 1

    ref_batch = BatchEncoder(chunk_size=32).encode(
        sigs, tables, domain_ids=doms
    )
    ref = Transcoder(chunk_size=32).transcode_to_host(
        ref_batch, tables, tables[0], dst_domain_ids=[0] * len(sigs)
    )
    assert _container_bytes(got) == _container_bytes(ref)


def test_sharded_batch_into_narrower_transcoder(tables, archive):
    """Placement follows the data: an EncodedBatch sharded over every
    visible device feeds a SINGLE-device Transcoder — each shard's stream
    stitches, decodes and re-encodes on the device that holds it, and the
    bytes still match the unsharded pipeline.  (Regression: this used to
    index the transcoder's (None,) device tuple with the source's shard
    ids and crash under multi-device.)"""
    sigs, doms, _ = archive
    devs = jax.local_devices()
    batch = BatchEncoder(devices=devs, chunk_size=64).encode(
        sigs, tables, domain_ids=doms
    )
    got = Transcoder(devices=None, chunk_size=64).transcode_to_host(
        batch, tables, tables[1], dst_domain_ids=[1] * len(sigs)
    )
    ref_batch = BatchEncoder(devices=None, chunk_size=64).encode(
        sigs, tables, domain_ids=doms
    )
    ref = Transcoder(devices=None, chunk_size=64).transcode_to_host(
        ref_batch, tables, tables[1], dst_domain_ids=[1] * len(sigs)
    )
    assert _container_bytes(got) == _container_bytes(ref)


def test_fused_kernels_byte_identical(tables, archive):
    """use_kernels=True (the fused Pallas megakernel decode + fused encode
    tile, interpret mode on CPU) is byte-identical to the XLA stage
    definitions across all three engines — under however many devices are
    visible, so the 4-fake-device CI leg pins the sharded + pipelined
    kernel path too."""
    sigs, doms, containers = archive

    ref = BatchDecoder(use_kernels=False).decode(containers, tables)
    got = BatchDecoder(use_kernels=True).decode(containers, tables)
    for a, b in zip(got.to_host(), ref.to_host()):
        np.testing.assert_array_equal(a, b)

    ref = BatchEncoder(use_kernels=False, chunk_size=64).encode(
        sigs, tables, domain_ids=doms
    ).to_host()
    got = BatchEncoder(use_kernels=True, chunk_size=64).encode(
        sigs, tables, domain_ids=doms
    ).to_host()
    assert _container_bytes(got) == _container_bytes(ref)

    ref = Transcoder(use_kernels=False, chunk_size=64).transcode_to_host(
        containers, tables, tables[1], dst_domain_ids=[1] * len(containers)
    )
    got = Transcoder(use_kernels=True, chunk_size=64).transcode_to_host(
        containers, tables, tables[1], dst_domain_ids=[1] * len(containers)
    )
    assert _container_bytes(got) == _container_bytes(ref)

    # device-resident EncodedBatch source: stitch + megakernel decode +
    # fused re-encode, all kernels, still the same bytes
    src_k = BatchEncoder(use_kernels=True, chunk_size=64).encode(
        sigs, tables, domain_ids=doms
    )
    got = Transcoder(use_kernels=True, chunk_size=64).transcode_to_host(
        src_k, tables, tables[0], dst_domain_ids=[0] * len(sigs)
    )
    src_x = BatchEncoder(use_kernels=False, chunk_size=64).encode(
        sigs, tables, domain_ids=doms
    )
    ref = Transcoder(use_kernels=False, chunk_size=64).transcode_to_host(
        src_x, tables, tables[0], dst_domain_ids=[0] * len(sigs)
    )
    assert _container_bytes(got) == _container_bytes(ref)


def test_pinned_shard_without_device_mapping_raises():
    sched = BucketScheduler(devices=None)
    with pytest.raises(ValueError, match="shard_devices"):
        sched.buckets(["x", "x"], shard_ids=[0, 3])


def test_fused_gather_compile_bound(tables):
    """The fused gather+encode jit must specialize on BUCKETED shapes only:
    two archives with different raw sample totals that round to the same
    power-of-two flat length (and the same word/window buckets) reuse one
    XLA executable — an unbucketed flat length would recompile the whole
    DCT+quant+pack per archive size."""
    from repro.serving.batch_encode import _encode_bucket_gather

    try:
        _encode_bucket_gather._cache_size()
    except AttributeError:  # pragma: no cover - older/newer jax
        pytest.skip("jit cache size not exposed")

    def migrate(lengths, seed):
        containers = [
            encode(make_signal("load_power", n, seed=seed + i), tables[0])
            for i, n in enumerate(lengths)
        ]
        Transcoder(chunk_size=64).transcode_to_host(
            containers, tables[0], tables[1],
            dst_domain_ids=[1] * len(lengths),
        )

    migrate([3000, 1200], seed=300)
    size1 = _encode_bucket_gather._cache_size()
    migrate([2990, 1190], seed=310)  # different totals, same buckets
    assert _encode_bucket_gather._cache_size() == size1


def test_mismatched_transcoder_devices_raise(tables):
    with pytest.raises(ValueError, match="same devices"):
        Transcoder(
            decoder=BatchDecoder(devices=None),
            encoder=BatchEncoder(devices=jax.local_devices()),
        )


# ---------------------------------------------------------------------------
# Bucket policies: padding ladders change scheduling only, never bytes.
# ---------------------------------------------------------------------------
def test_bucket_policies_byte_identical(tables, archive):
    """All three bucket-edge ladders produce the same bytes: decoded
    samples always; encode/transcode streams in exact (unchunked) packing
    mode, where the word stream is independent of the bucket a signal
    landed in.  (Chunked packing legitimately varies with the window
    bucket — that contract is chunk padding, not policy.)"""
    sigs, doms, containers = archive
    ref = None
    for pol in POLICY_NAMES:
        dec = BatchDecoder(policy=pol)
        got_dec = [
            np.asarray(s) for s in dec.decode(containers, tables).to_host()
        ]
        assert dec.scheduler.policy.name == pol
        enc = BatchEncoder(policy=pol, chunk_size=None)
        got_enc = _container_bytes(
            enc.encode(sigs, tables, domain_ids=doms).to_host()
        )
        tc = Transcoder(policy=pol, chunk_size=None)
        got_tc = _container_bytes(
            tc.transcode_to_host(
                containers, tables, tables[1],
                dst_domain_ids=[1] * len(containers),
            )
        )
        if ref is None:
            ref = (got_dec, got_enc, got_tc)
            # exact-mode engine encode == the host reference codec
            assert got_enc == [
                encode(s, tables[d]).to_bytes()
                for s, d in zip(sigs, doms)
            ]
        else:
            for a, b in zip(got_dec, ref[0]):
                np.testing.assert_array_equal(a, b)
            assert got_enc == ref[1]
            assert got_tc == ref[2]


def test_mismatched_transcoder_policies_raise():
    with pytest.raises(ValueError, match="same bucket policy"):
        Transcoder(
            decoder=BatchDecoder(policy="p2"),
            encoder=BatchEncoder(policy="half-octave"),
        )


@pytest.mark.parametrize("pol", POLICY_NAMES)
def test_policy_compile_count_bounded(tables, pol):
    """Every policy's ladder keeps the fused-decode jit specializing on
    BUCKET edges only: archives with slightly different raw word/window
    totals that round to the same edges reuse the same executables, and a
    repeat of the same archive compiles nothing."""
    from repro.serving.batch_decode import bucket_cache_size

    if bucket_cache_size() is None:
        pytest.skip("jit cache size not exposed")

    def archive_of(lengths, seed):
        return [
            encode(make_signal("load_power", n, seed=seed + i), tables[0])
            for i, n in enumerate(lengths)
        ]

    dec = BatchDecoder(policy=pol)
    a1 = archive_of([3000, 1200, 5000], seed=500)
    dec.decode(a1, tables).to_host()
    size1 = bucket_cache_size()
    # nearby totals, same bucket edges under every ladder (seeds chosen so
    # the symlen bucket — a policy-independent static — matches too)
    # -> zero new compiles
    a2 = archive_of([2990, 1195, 4990], seed=520)
    dec.decode(a2, tables).to_host()
    assert bucket_cache_size() == size1
    dec.decode(a1, tables).to_host()
    assert bucket_cache_size() == size1


# ---------------------------------------------------------------------------
# Tuning cache: tuned kernel blocks retile dispatches, never change bytes.
# ---------------------------------------------------------------------------
def test_tuning_cache_warm_vs_cold_byte_identical(tables, archive, tmp_path):
    """Kernel-path engines under a COLD tuning cache (built-in block
    sizes) and again after the cache learns non-default blocks for the
    exact (plan key, bucket shape) entries the engines consult: the store
    bumps the epoch, the bucket jits retrace, the trace-time consult hits
    — and the bytes are identical."""
    from repro.serving.engine import symlen_bucket
    from repro.tuning import autotune

    sigs, doms, containers = archive
    backend = jax.default_backend()
    cache = autotune.TuningCache(str(tmp_path))
    autotune.set_default_cache(cache)
    try:
        dec = BatchDecoder(use_kernels=True)
        enc = BatchEncoder(use_kernels=True, chunk_size=64)
        cold_dec = [
            np.asarray(s) for s in dec.decode(containers, tables).to_host()
        ]
        cold_enc = _container_bytes(
            enc.encode(sigs, tables, domain_ids=doms).to_host()
        )

        # hand-tune non-default blocks under the EXACT keys the engines'
        # buckets consult at trace time
        e0 = autotune.epoch()
        groups = defaultdict(list)
        for c in containers:
            groups[c.plan_key].append(c)
        for key, cs in groups.items():
            c0 = cs[0]
            wp = dec.scheduler.round(sum(c.num_words for c in cs))
            winp = dec.scheduler.round(
                max(sum(c.num_windows for c in cs), 1)
            )
            ms = symlen_bucket(max(c.max_symlen for c in cs))
            cache.store(
                "decode", backend, (c0.n, c0.e, c0.l_max, ms), (wp, winp),
                {"block_words": 256, "block_windows": 128},
            )
        enc_groups = defaultdict(list)
        for s, d in zip(sigs, doms):
            cfg = tables[d].config
            nwin = -(-len(s) // cfg.n)
            wb = enc.scheduler.round(max(nwin, 1))
            enc_groups[(d, wb)].append(s)
        for (d, wb), members in enc_groups.items():
            cfg = tables[d].config
            sp = wb * cfg.e
            kp = enc.scheduler.round(len(members))
            cache.store(
                "encode", backend, (cfg.l_max, min(64, sp)), (kp, sp),
                {"block_lanes": 3},  # pads the lane axis inside the kernel
            )
        assert autotune.epoch() > e0

        hits0 = cache.hits
        warm_dec = [
            np.asarray(s) for s in dec.decode(containers, tables).to_host()
        ]
        warm_enc = _container_bytes(
            enc.encode(sigs, tables, domain_ids=doms).to_host()
        )
        # the consult actually HIT the stored entries (guards this test
        # against silently drifting out of sync with the ops.py keys)
        assert cache.hits > hits0

        for a, b in zip(warm_dec, cold_dec):
            np.testing.assert_array_equal(a, b)
        assert warm_enc == cold_enc
    finally:
        autotune.set_default_cache(None)


# ---------------------------------------------------------------------------
# Transfer guard: pipelining adds no d2h syncs before the drain.
# ---------------------------------------------------------------------------
def test_pipelining_adds_no_d2h_before_drain(tables, archive, monkeypatch):
    """Acceptance: with pipelining (and whatever sharding is visible) on,
    the decode -> re-encode pipeline performs ZERO device->host transfers
    before the explicit drain.  The jax transfer guard is set process-wide
    (the staging worker thread would escape a thread-local context
    manager); because same-platform CPU 'transfers' may not register with
    the guard, the drain entry points themselves are instrumented too —
    exactly one must run, at to_host()."""
    _, _, containers = archive
    drains = {"n": 0}
    real_fetch = batch_decode_mod.fetch_to_host
    real_stitched = batch_encode_mod.fetch_to_host_stitched

    def counting_fetch(arrays):
        drains["n"] += 1
        return real_fetch(arrays)

    def counting_stitched(bucket_arrays, stitch):
        drains["n"] += 1
        return real_stitched(bucket_arrays, stitch)

    monkeypatch.setattr(batch_decode_mod, "fetch_to_host", counting_fetch)
    monkeypatch.setattr(
        batch_encode_mod, "fetch_to_host_stitched", counting_stitched
    )

    tc = Transcoder(pipeline=True)
    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    try:
        out = tc.transcode(containers, tables, tables[1],
                           dst_domain_ids=[1] * len(containers))
        out.block_until_ready()  # device sync, not a transfer
        assert drains["n"] == 0
    finally:
        jax.config.update("jax_transfer_guard_device_to_host", None)
    migrated = out.to_host()
    assert drains["n"] == 1  # the single drain
    assert len(migrated) == len(containers)
