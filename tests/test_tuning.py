"""The autotuning layer: the persisted TuningCache (hit/reject/concurrency
contracts), the tune() sweep, the BucketPolicy ladders and the cost model
the scheduler's shape decisions ride on.

Byte-identity of tuned kernels and the policy/compile-count contracts of
the live engines are pinned in tests/test_engine.py; this file covers the
tuning package's own units.
"""
import json
import os
import threading

import pytest

from repro.tuning.autotune import (
    CACHE_VERSION,
    TuningCache,
    decode_block_candidates,
    encode_block_candidates,
    epoch,
    set_default_cache,
    tune,
    tuned_blocks,
)
from repro.tuning.cost_model import PEAKS, CostModel, default_cost_model
from repro.tuning.policy import (
    BucketPolicy,
    COST_BALANCED,
    HALF_OCTAVE,
    P2,
    POLICY_NAMES,
    cost_balanced_policy,
)


# ---------------------------------------------------------------------------
# TuningCache: store/lookup, persistence, rejection of bad state.
# ---------------------------------------------------------------------------
def test_cache_roundtrip_and_persistence(tmp_path):
    cache = TuningCache(str(tmp_path))
    assert cache.lookup("decode", "cpu", (32, 6), (1024, 256)) is None
    cache.store(
        "decode", "cpu", (32, 6), (1024, 256),
        {"block_words": 512, "block_windows": 128},
    )
    assert cache.lookup("decode", "cpu", (32, 6), (1024, 256)) == {
        "block_words": 512, "block_windows": 128
    }
    # a different shape is a different entry
    assert cache.lookup("decode", "cpu", (32, 6), (2048, 256)) is None

    # a fresh instance reads the persisted file
    again = TuningCache(str(tmp_path))
    assert len(again) == 1
    assert again.lookup("decode", "cpu", (32, 6), (1024, 256)) == {
        "block_words": 512, "block_windows": 128
    }
    with open(cache.path) as f:
        data = json.load(f)
    assert data["version"] == CACHE_VERSION


def test_cache_memory_only_without_directory(monkeypatch):
    monkeypatch.delenv("FPTC_TUNING_CACHE", raising=False)
    cache = TuningCache()
    assert cache.path is None
    cache.store("encode", "cpu", (32, 6, 64), (8, 1024), {"block_rows": 4})
    assert cache.lookup("encode", "cpu", (32, 6, 64), (8, 1024)) == {
        "block_rows": 4
    }


def test_corrupt_cache_file_rejected_not_trusted(tmp_path):
    path = tmp_path / "fptc_tuning.json"
    path.write_text("{ not json !!!")
    cache = TuningCache(str(tmp_path))
    assert cache.lookup("decode", "cpu", (32,), (64,)) is None  # no raise
    # the cache stays writable and overwrites the corrupt file
    cache.store("decode", "cpu", (32,), (64,), {"block_words": 64})
    again = TuningCache(str(tmp_path))
    assert again.lookup("decode", "cpu", (32,), (64,)) == {"block_words": 64}


def test_stale_schema_version_rejected_wholesale(tmp_path):
    path = tmp_path / "fptc_tuning.json"
    path.write_text(json.dumps({
        "version": CACHE_VERSION + 999,
        "entries": {
            "decode|cpu|plan(32)|shape(64)": {"blocks": {"block_words": 64}}
        },
    }))
    cache = TuningCache(str(tmp_path))
    assert len(cache) == 0
    assert cache.lookup("decode", "cpu", (32,), (64,)) is None


def test_invalid_entries_dropped_and_retuned(tmp_path):
    path = tmp_path / "fptc_tuning.json"
    path.write_text(json.dumps({
        "version": CACHE_VERSION,
        "entries": {
            # block size 0, a string block, a bool, and a missing map
            "decode|cpu|plan(1)|shape(1)": {"blocks": {"block_words": 0}},
            "decode|cpu|plan(2)|shape(2)": {"blocks": {"block_words": "x"}},
            "decode|cpu|plan(3)|shape(3)": {"blocks": {"block_words": True}},
            "decode|cpu|plan(4)|shape(4)": {},
            "decode|cpu|plan(5)|shape(5)": {"blocks": {"block_words": 32}},
        },
    }))
    cache = TuningCache(str(tmp_path))
    assert len(cache) == 1  # only the valid entry survives the load
    for plan in (1, 2, 3, 4):
        assert cache.lookup("decode", "cpu", (plan,), (plan,)) is None
    assert cache.lookup("decode", "cpu", (5,), (5,)) == {"block_words": 32}


def test_store_refuses_invalid_blocks(tmp_path):
    cache = TuningCache(str(tmp_path))
    for bad in ({}, {"block_words": 0}, {"block_words": "big"}, "nope"):
        with pytest.raises((ValueError, TypeError)):
            cache.store("decode", "cpu", (1,), (1,), bad)
    assert len(cache) == 0


def test_store_bumps_epoch(tmp_path):
    cache = TuningCache(str(tmp_path))
    e0 = epoch()
    cache.store("decode", "cpu", (1,), (1,), {"block_words": 8})
    assert epoch() > e0


def test_concurrent_readers_and_writers_safe(tmp_path):
    """The PlanCache discipline: N reader threads race a writer through
    lookup/store with file IO underneath — no exceptions, and every
    observed value is a valid stored entry."""
    cache = TuningCache(str(tmp_path))
    cache.store("decode", "cpu", (0,), (0,), {"block_words": 1})
    errors = []
    seen = set()
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                got = cache.lookup("decode", "cpu", (0,), (0,))
                if got is not None:
                    seen.add(got["block_words"])
        except Exception as exc:  # pragma: no cover - the failure signal
            errors.append(exc)

    def writer():
        try:
            for i in range(1, 50):
                cache.store(
                    "decode", "cpu", (0,), (0,), {"block_words": i}
                )
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    w = threading.Thread(target=writer)
    for t in readers:
        t.start()
    w.start()
    w.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors
    assert seen <= set(range(1, 50)) | {1}
    # the persisted file is whole and valid after the race (atomic replace)
    again = TuningCache(str(tmp_path))
    assert again.lookup("decode", "cpu", (0,), (0,)) == {"block_words": 49}


# ---------------------------------------------------------------------------
# tune(): the sweep contract.
# ---------------------------------------------------------------------------
def test_tune_hit_returns_without_running(tmp_path):
    cache = TuningCache(str(tmp_path))
    cache.store(
        "decode", "cpu", (32,), (64, 64), {"block_words": 512},
    )
    calls = []
    got = tune(
        "decode", (32,), (64, 64),
        runner=lambda blocks: calls.append(blocks),
        candidates=[{"block_words": 1}, {"block_words": 2}],
        cache=cache, backend="cpu",
    )
    assert got == {"block_words": 512}
    assert calls == []  # the hit path never executed a candidate


def test_tune_force_retunes_and_stores(tmp_path):
    cache = TuningCache(str(tmp_path))
    cache.store("decode", "cpu", (32,), (64, 64), {"block_words": 512})
    calls = []
    cands = [{"block_words": 1}, {"block_words": 2}]
    got = tune(
        "decode", (32,), (64, 64),
        runner=calls.append, candidates=cands,
        cache=cache, backend="cpu", force=True, trials=1, warmup=0,
    )
    assert got in cands
    assert calls  # the sweep actually ran
    assert cache.lookup("decode", "cpu", (32,), (64, 64)) == got


def test_tune_rank_and_top_k_prune_the_sweep(tmp_path):
    cache = TuningCache(str(tmp_path))
    cands = [{"block_words": w} for w in (1, 2, 4, 8)]
    calls = []
    got = tune(
        "decode", (33,), (64, 64),
        runner=calls.append, candidates=cands,
        cache=cache, backend="cpu", trials=1, warmup=0,
        rank=lambda b: -b["block_words"],  # model says: biggest first
        top_k=1,
    )
    assert got == {"block_words": 8}
    assert calls == [{"block_words": 8}]  # pruned to the model's pick


def test_tune_requires_candidates(tmp_path):
    cache = TuningCache(str(tmp_path))
    with pytest.raises(ValueError, match="candidate"):
        tune("decode", (1,), (1,), lambda b: None, [], cache=cache,
             backend="cpu")


def test_tuned_blocks_consults_pinned_default_cache(tmp_path):
    import jax

    backend = jax.default_backend()
    cache = TuningCache(str(tmp_path))
    set_default_cache(cache)
    try:
        assert tuned_blocks("decode", (32, 6), (128, 64)) == {}
        cache.store(
            "decode", backend, (32, 6), (128, 64), {"block_words": 64}
        )
        assert tuned_blocks("decode", (32, 6), (128, 64)) == {
            "block_words": 64
        }
    finally:
        set_default_cache(None)


def test_block_candidates_clip_and_dedupe():
    for c in decode_block_candidates(100, 50):
        assert c["block_words"] <= 100 and c["block_windows"] <= 50
    small = decode_block_candidates(1, 1)
    assert small == [{"block_words": 1, "block_windows": 1}]
    assert encode_block_candidates(300) == [
        {"block_lanes": 128}, {"block_lanes": 256}, {"block_lanes": 300}
    ]


# ---------------------------------------------------------------------------
# BucketPolicy ladders.
# ---------------------------------------------------------------------------
def test_policy_round_contracts():
    for pol in (P2, HALF_OCTAVE, COST_BALANCED):
        prev = 0
        for x in (1, 2, 3, 5, 7, 12, 100, 1000, 4097):
            r = pol.round(x)
            assert r >= x  # never below the input
            assert pol.round(r) == r  # idempotent on edges
            assert r >= prev  # monotone
            prev = r
    # p2 parity with the engine's historical rounding
    from repro.serving.engine import p2

    for x in (1, 2, 3, 5, 100, 1000, 4097):
        assert P2.round(x) == p2(x)
    assert HALF_OCTAVE.round(5) == 6
    assert HALF_OCTAVE.round(100) == 128
    assert COST_BALANCED.round(5) == 5


def test_policy_variant_bound_is_density_times_octaves():
    hi = 1 << 16
    p2_variants = P2.max_variants(1, hi)
    assert p2_variants <= 17
    assert HALF_OCTAVE.max_variants(1, hi) <= 2 * p2_variants
    assert COST_BALANCED.max_variants(1, hi) <= (
        len(COST_BALANCED.multipliers) * p2_variants
    )


def test_policy_resolution_and_env(monkeypatch):
    assert BucketPolicy.of(P2) is P2
    assert BucketPolicy.of("half_octave") is HALF_OCTAVE  # normalized
    monkeypatch.setenv("FPTC_BUCKET_POLICY", "cost-balanced")
    assert BucketPolicy.of(None) is COST_BALANCED
    monkeypatch.delenv("FPTC_BUCKET_POLICY")
    assert BucketPolicy.of(None) is P2
    with pytest.raises(ValueError, match="unknown bucket policy"):
        BucketPolicy.of("bogus")


def test_policy_validates_multipliers():
    with pytest.raises(ValueError):
        BucketPolicy("empty", ())
    with pytest.raises(ValueError):
        BucketPolicy("bad", (2.0,))
    with pytest.raises(ValueError):
        BucketPolicy("bad", (0.5,))


def test_cost_balanced_ladder_from_model():
    pol = cost_balanced_policy()
    d = len(pol.multipliers)
    assert 1 <= d <= 4
    assert pol.multipliers[0] == 1.0
    assert all(
        pol.multipliers[i] < pol.multipliers[i + 1] for i in range(d - 1)
    )
    assert POLICY_NAMES == ("p2", "half-octave", "cost-balanced")


# ---------------------------------------------------------------------------
# Cost model.
# ---------------------------------------------------------------------------
def test_cost_model_monotone_in_shape():
    cm = CostModel(device_kind="cpu")
    base = cm.decode_bucket_cost(1024, 256, e=6, n=32)
    assert cm.decode_bucket_cost(2048, 256, e=6, n=32) > base
    assert cm.decode_bucket_cost(1024, 512, e=6, n=32) > base
    enc = cm.encode_bucket_cost(8, 128, e=6, n=32)
    assert cm.encode_bucket_cost(16, 128, e=6, n=32) > enc
    assert cm.signal_decode_cost(100, 50, e=6, n=32) > 0
    assert cm.signal_encode_cost(50, e=6, n=32) > 0


def test_cost_model_seed_rescales():
    cm = CostModel(device_kind="cpu")
    raw = cm.signal_decode_cost(100, 50, e=6, n=32)
    cm.seed(
        "decode",
        2.0 * cm.decode_flops(100, 50, e=6, n=32),
        cm.decode_bytes(100, 50, e=6, n=32),
        words=100, windows=50, e=6, n=32,
    )
    assert cm.signal_decode_cost(100, 50, e=6, n=32) == pytest.approx(
        2.0 * raw
    )


def test_cost_model_observe_calibrates():
    cm = CostModel(device_kind="cpu")
    t = cm.decode_bucket_cost(1024, 256, e=6, n=32)
    cm.observe("decode", predicted_s=1.0, measured_s=3.0)
    assert cm.calibration("decode") == pytest.approx(3.0)
    assert cm.decode_bucket_cost(1024, 256, e=6, n=32) == pytest.approx(
        3.0 * t
    )
    cm.observe("decode", predicted_s=0.0, measured_s=1.0)  # ignored
    assert cm.calibration("decode") == pytest.approx(3.0)


def test_edges_per_octave_bounded():
    for kind in PEAKS:
        d = CostModel(device_kind=kind).edges_per_octave()
        assert 1 <= d <= 4
    assert default_cost_model() is default_cost_model()
