"""The decode engine over four devices of one process
(``BatchDecoder(devices="auto")``): the same bits as one device, every
dispatch's record naming its device, and one ``fptc.dispatch`` host span
per (group, shard) dispatch while the profiler traces.

JAX may already be running in this process with one device, so the four
CPU devices live in a child process
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), which prints
what it saw as one JSON line."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

CHILD = r'''
import glob, json, sys
import jax
import numpy as np
from jax.profiler import ProfileData

import repro.serving.engine as engine
from repro.core import DOMAIN_DEFAULTS, calibrate, encode
from repro.data import make_signal
from repro.serving import BatchDecoder
from repro.serving.engine import BucketScheduler, symlen_bucket

log_dir = sys.argv[1]
# (domain, dataset, strips): the meteorological group has fewer strips
# than devices
DOMAINS = [("biomedical", "ecg_arth", 9), ("seismic", "seismic", 6),
           ("power", "load_power", 5), ("meteorological", "temperature", 2)]
tables, containers = {}, []
for d, (dom, ds, count) in enumerate(DOMAINS):
    tables[d] = calibrate(make_signal(ds, 16384, seed=d),
                          DOMAIN_DEFAULTS[dom], domain_id=d)
    containers += [encode(make_signal(ds, 1000 + 397 * k, seed=100 * d + k),
                          tables[d]) for k in range(count)]
order = np.random.default_rng(0).permutation(len(containers))
containers = [containers[i] for i in order]


class Counted(engine.TraceAnnotation):
    """The profiler's annotation, counting the dispatch spans made."""

    made = 0

    def __init__(self, name, **stats):
        if name == "fptc.dispatch":
            type(self).made += 1
        super().__init__(name, **stats)


engine.TraceAnnotation = Counted

one = BatchDecoder(devices=None).decode(containers, tables).to_host()
four = BatchDecoder(devices="auto")
batch = four.decode(containers, tables)
got = batch.to_host()
untraced = Counted.made
# the drain contract on four devices: read-only float32 views, bit-equal
# to the device signals, no two sharing memory, nothing copied
views = {
    "device_equal": all(np.array_equal(y, np.array(batch.device_signal(i)))
                        for i, y in enumerate(got)),
    "layout": all(y.dtype == np.float32 and y.flags.c_contiguous
                  and y.shape == (c.signal_length,) and not y.flags.writeable
                  for y, c in zip(got, containers)),
    "overlaps": sum(np.shares_memory(a, b) for i, a in enumerate(got)
                    for b in got[i + 1:]),
    "strip_bytes": sum(y.nbytes for y in got),
    "drain_bytes": four.stats.drain_bytes,
    "drain_bytes_copied": four.stats.drain_bytes_copied,
}

jax.profiler.start_trace(log_dir)
try:
    d0 = four.stats.dispatches
    four.decode(containers, tables).to_host()
    traced_dispatches = four.stats.dispatches - d0
finally:
    jax.profiler.stop_trace()
path = glob.glob(log_dir + "/**/*.xplane.pb", recursive=True)[0]
spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host") for line in plane.lines
         for ev in line.events if ev.name == "fptc.dispatch"]

# where the scheduler puts each bucket, found apart from the records
sched = BucketScheduler(devices="auto")
costs = [four.cost_model.signal_decode_cost(
    c.num_words, c.num_windows, e=c.e, n=c.n,
    max_symlen=symlen_bucket(c.max_symlen)) for c in containers]
placed = {}
for b in sched.buckets([c.plan_key for c in containers], item_costs=costs):
    dev = 0 if b.device is None else b.device.id
    words = sched.round(sum(containers[i].num_words for i in b.items))
    placed[str(dev)] = placed.get(str(dev), 0) + words

pads = list(four.stats.bucket_pad)
print(json.dumps({
    "devices": [d.id for d in jax.local_devices()],
    "strips": len(containers),
    "bit_equal": all(a.shape == b.shape and np.array_equal(a, b)
                     for a, b in zip(one, got)),
    "records": [{"device": p.get("device"), "shard": p["shard"],
                 "words_padded": p["words_padded"], "arm": p["arm"]}
                for p in pads],
    "placed": placed,
    "untraced_spans": untraced,
    "traced_dispatches": traced_dispatches,
    "traced_made": Counted.made - untraced,
    "spans": [{k: s.get(k) for k in ("shard", "device", "arm",
                                      "words_padded")} for s in spans],
    "views": views,
}))
'''


@pytest.fixture(scope="module")
def drained(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    log_dir = tmp_path_factory.mktemp("trace")
    res = subprocess.run([sys.executable, "-c", CHILD, str(log_dir)],
                         env=env, capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # the decoder's records: the untraced decode's, then the traced one's
    half = len(out["records"]) // 2
    out["first"], out["second"] = out["records"][:half], out["records"][half:]
    return out


def test_four_devices_decode_the_same_bits_as_one(drained):
    assert drained["devices"] == [0, 1, 2, 3]
    assert drained["bit_equal"]


def test_every_record_names_one_of_the_four_devices(drained):
    recs = drained["first"]
    # 9, 6 and 5 strips split over all four devices, 2 strips over two
    assert len(recs) == 4 + 4 + 4 + 2
    assert {r["device"] for r in recs} == {0, 1, 2, 3}
    assert drained["second"] == recs  # the same placement every pass


def test_padded_words_per_device_add_up(drained):
    per = {}
    for r in drained["first"]:
        per[str(r["device"])] = per.get(str(r["device"]), 0) + r["words_padded"]
    assert per == drained["placed"]
    assert sum(per.values()) == sum(r["words_padded"] for r in drained["first"])


def test_one_dispatch_span_per_dispatch_while_tracing(drained):
    assert drained["untraced_spans"] == 0  # none while the profiler is off
    assert drained["traced_made"] == drained["traced_dispatches"] == 14
    assert sorted((s["shard"], s["device"], s["words_padded"])
                  for s in drained["spans"]) == sorted(
        (r["shard"], r["device"], r["words_padded"])
        for r in drained["second"])
    assert {s["arm"] for s in drained["spans"]} == {"xla"}  # the CPU's arm


def test_four_device_strips_are_read_only_views_of_the_device_signals(drained):
    v = drained["views"]
    assert v["device_equal"] and v["layout"]
    assert v["overlaps"] == 0


def test_four_device_drain_counts_its_bytes_and_copies_none(drained):
    v = drained["views"]
    assert v["drain_bytes"] == v["strip_bytes"] > 0
    assert v["drain_bytes_copied"] == 0
