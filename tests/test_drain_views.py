"""The drain contract of ``DecodedBatch.to_host``: each strip comes back as
a read-only view of its bucket's host array (no per-strip copy), bit-equal
to the device signal, in input order, never overlapping another strip of
the drain and never changed by a later decode; the decoder's stats count
the bytes handed back and the bytes copied to hand them back (none, unless
a host copy comes back strided).  The one-signal helper
``codec.decode_device`` still returns an array its caller owns.  (The four-device drain is in ``test_sharded_drain.py``.)"""
import gc
import itertools
import os
import sys
import threading

import numpy as np
import pytest

from repro.core import DOMAIN_DEFAULTS, calibrate, decode_device, encode
from repro.data import make_signal
import repro.serving.batch_decode as batch_decode
from repro.serving.batch_decode import BatchDecoder, BatchDecoderStats
from repro.serving.quarantine import PoisonedContainerError

# (domain id, dataset, length) of each strip, domains interleaved so that
# input order differs from the bucket order
STRIPS = [(0, "load_power", 4096), (1, "temperature", 2048),
          (0, "load_power", 5000), (1, "temperature", 3001),
          (0, "load_power", 333), (1, "temperature", 1024),
          (0, "load_power", 8191)]


@pytest.fixture(scope="module")
def tables():
    return {
        0: calibrate(make_signal("load_power", 16384, seed=7),
                     DOMAIN_DEFAULTS["power"], domain_id=0),
        1: calibrate(make_signal("temperature", 16384, seed=8),
                     DOMAIN_DEFAULTS["meteorological"], domain_id=1),
    }


def _archive(tables, seed):
    return [encode(make_signal(ds, n, seed=seed + k), tables[d])
            for k, (d, ds, n) in enumerate(STRIPS)]


@pytest.fixture(scope="module")
def drained(tables):
    dec = BatchDecoder(devices=None)
    batch = dec.decode(_archive(tables, seed=100), tables)
    return dec, batch, batch.to_host()


def test_strips_are_bit_equal_to_the_device_signals(drained):
    _, batch, out = drained
    assert [y.shape for y in out] == [(n,) for _, _, n in STRIPS]
    for i, y in enumerate(out):
        assert np.array_equal(y, np.array(batch.device_signal(i)))


@pytest.mark.parametrize("i", range(len(STRIPS)))
def test_each_strip_is_a_read_only_float32_view(drained, i):
    y = drained[2][i]
    assert y.dtype == np.float32 and y.shape == (STRIPS[i][2],)
    assert y.flags.c_contiguous
    assert not y.flags.writeable and not y.flags.owndata


def _tpu_host_copies(arrays):
    """``fetch_to_host`` as a TPU answers it: the host copy keeps the
    device layout, and a TPU lays a narrow 2-D f32 array out column-major
    (minor dimension 0), so it arrives Fortran-ordered and read-only."""
    out = []
    for a in arrays:
        h = np.asarray(a)
        if h.ndim == 2:
            h = np.asfortranarray(h)
            h.flags.writeable = False
        out.append(h)
    return out


def test_strips_stay_views_where_the_host_copy_is_column_major(
        tables, monkeypatch):
    monkeypatch.setattr(batch_decode, "fetch_to_host", _tpu_host_copies)
    dec = BatchDecoder(devices=None)
    batch = dec.decode(_archive(tables, seed=800), tables)
    out = batch.to_host()
    for i, y in enumerate(out):
        assert np.array_equal(y, np.array(batch.device_signal(i)))
        assert y.flags.c_contiguous
        assert not y.flags.writeable and not y.flags.owndata
    assert dec.stats.drain_bytes_copied == 0


def test_each_bucket_is_drained_as_one_flat_sample_tensor(drained):
    _, batch, _ = drained
    samples, windows = batch.device_samples, batch.device_windows
    assert len(samples) == len(windows) >= 2
    for flat, win in zip(samples, windows):
        assert flat.ndim == 1 and win.ndim == 2
        assert np.array_equal(np.array(win).reshape(-1), np.array(flat))


def _strided_host_copies(arrays):
    """``fetch_to_host`` answering with read-only, every-other-element
    views of larger host buffers: 1-D copies that are not contiguous."""
    out = []
    for a in arrays:
        h = np.repeat(np.asarray(a), 2)[::2]
        h.flags.writeable = False
        out.append(h)
    return out


def test_a_strided_host_copy_is_made_contiguous_once_and_counted(
        tables, monkeypatch):
    monkeypatch.setattr(batch_decode, "fetch_to_host", _strided_host_copies)
    dec = BatchDecoder(devices=None)
    batch = dec.decode(_archive(tables, seed=800), tables)
    out = batch.to_host()
    for i, y in enumerate(out):
        assert np.array_equal(y, np.array(batch.device_signal(i)))
        assert y.flags.c_contiguous
        assert not y.flags.writeable and not y.flags.owndata
    assert dec.stats.drain_bytes == sum(y.nbytes for y in out)
    assert dec.stats.drain_bytes_copied == sum(
        int(g.nbytes) for g in batch.device_samples)


def test_writing_a_strip_fails_loudly(drained):
    y = drained[2][0]
    before = np.array(y)
    with pytest.raises(ValueError, match="read-only"):
        y[0] = 1.0
    assert np.array_equal(y, before)


def test_no_two_strips_of_a_drain_share_memory(drained):
    out = drained[2]
    for a, b in itertools.combinations(out, 2):
        assert not np.shares_memory(a, b)


def test_a_held_strip_survives_later_decodes_and_drains(tables):
    dec = BatchDecoder(devices=None)
    batch = dec.decode(_archive(tables, seed=200), tables)
    held = batch.to_host()
    want = [np.array(y) for y in held]
    del batch
    gc.collect()
    for seed in (300, 400):  # the same lengths and buckets, other samples
        later = dec.decode(_archive(tables, seed=seed), tables).to_host()
        gc.collect()
        assert not any(np.array_equal(a, b) for a, b in zip(later, want))
    for y, w in zip(held, want):
        assert np.array_equal(y, w)


def test_quarantined_positions_keep_order_and_their_typed_error(tables):
    cs = _archive(tables, seed=500)
    dec = BatchDecoder(devices=None)
    clean = dec.decode(cs, tables).to_host()
    items = [c.to_bytes() for c in cs]
    items[1] = items[1][:9]  # truncated
    items[4] = b"not a container"
    before = dec.stats.drain_bytes
    out = dec.decode(items, tables, quarantine=True).to_host()
    assert len(out) == len(cs)
    for i, y in enumerate(out):
        if i in (1, 4):
            assert isinstance(y, PoisonedContainerError) and y.index == i
        else:
            assert np.array_equal(y, clean[i]) and not y.flags.writeable
    assert dec.stats.drain_bytes - before == sum(
        out[i].nbytes for i in range(len(cs)) if i not in (1, 4))
    assert dec.stats.drain_bytes_copied == 0


def test_drain_stats_count_handed_back_bytes_and_no_copies(tables):
    dec = BatchDecoder(devices=None)
    cs = _archive(tables, seed=600)
    out = dec.decode(cs, tables).to_host()
    assert dec.stats.dispatches >= 2  # one bucket per domain at least
    strips = sum(y.nbytes for y in out)
    assert strips == 4 * sum(n for _, _, n in STRIPS)
    assert (dec.stats.drain_bytes, dec.stats.drain_bytes_copied) == (strips, 0)
    dec.decode(cs, tables).to_host()
    assert (dec.stats.drain_bytes, dec.stats.drain_bytes_copied) == (
        2 * strips, 0)


def test_an_empty_drain_counts_nothing():
    dec = BatchDecoder(devices=None)
    assert dec.decode([], {}).to_host() == []
    assert (dec.stats.drain_bytes, dec.stats.drain_bytes_copied) == (0, 0)


def test_decode_device_returns_a_writable_array_it_owns(tables):
    c = encode(make_signal("load_power", 3000, seed=700), tables[0])
    y = decode_device(c, tables[0])
    assert y.dtype == np.float32 and y.shape == (3000,)
    assert y.flags.writeable and y.flags.owndata and y.flags.c_contiguous
    want = BatchDecoder(devices=None).decode([c], tables[0]).to_host()[0]
    assert np.array_equal(y, want)
    y[0] = 1.0  # the caller's own array


def test_drain_counters_lose_no_update_across_threads():
    # the frontend's drain pool may drain batches of one decoder at once
    stats = BatchDecoderStats()
    workers, rounds = min(os.cpu_count() or 1, 32) + 2, 2000
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [stats.count_drain(4, copied=1)
                            for _ in range(rounds)]) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert (stats.drain_bytes, stats.drain_bytes_copied) == (
        4 * workers * rounds, workers * rounds)
