"""Program spans and device scopes of the decode path: the host spans
(``fptc.*`` profiler annotations, live only while ``jax.profiler`` traces)
and the ``fptc.decode.*`` named scopes inside the XLA arm of the decode
bucket program."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import repro.serving.engine as engine_mod
from repro.serving import BatchDecoder
from repro.serving.batch_decode import _decode_bucket
from repro.serving.engine import span, symlen_bucket

from _synth import uniform_code_container

HOST_SPANS = ("fptc.schedule", "fptc.stage", "fptc.dispatch",
              "fptc.drain.d2h", "fptc.drain.stitch")
DEVICE_SCOPES = ("fptc.decode.huffman", "fptc.decode.compact",
                 "fptc.decode.idct")


class _Recorder:
    """Stands in for ``TraceAnnotation``: counts the annotations made."""

    enabled = False
    made = []

    def __init__(self, name, **stats):
        type(self).made.append((name, stats))

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("enabled", [False, True])
def test_span_annotates_only_while_tracing(monkeypatch, enabled):
    monkeypatch.setattr(_Recorder, "enabled", enabled)
    monkeypatch.setattr(_Recorder, "made", [])
    monkeypatch.setattr(engine_mod, "TraceAnnotation", _Recorder)
    computed = []

    def nbytes():
        computed.append(1)
        return 64

    with span("fptc.test", bytes=nbytes, fixed=3):
        pass
    if enabled:
        assert _Recorder.made == [("fptc.test", {"bytes": 64, "fixed": 3})]
        assert computed == [1]
    else:
        assert _Recorder.made == []
        assert computed == []  # no stat is computed with the profiler off


def test_stitch_span_carries_the_strips_bytes(monkeypatch):
    monkeypatch.setattr(_Recorder, "enabled", True)
    monkeypatch.setattr(_Recorder, "made", [])
    monkeypatch.setattr(engine_mod, "TraceAnnotation", _Recorder)
    c0, tables = uniform_code_container(24, seed=1)
    c1, _ = uniform_code_container(40, seed=2)
    dec = BatchDecoder(devices=None)
    out = dec.decode([c0, c1, c0], tables).to_host()
    stitch = [st for n, st in _Recorder.made if n == "fptc.drain.stitch"]
    assert stitch == [{"bytes": sum(y.nbytes for y in out)}]
    assert dec.stats.drain_bytes == stitch[0]["bytes"]


def test_span_is_a_trace_annotation_inside_a_trace(tmp_path):
    assert not isinstance(span("fptc.test"), jax.profiler.TraceAnnotation)
    jax.profiler.start_trace(str(tmp_path))
    try:
        live = span("fptc.test")
    finally:
        jax.profiler.stop_trace()
    assert isinstance(live, jax.profiler.TraceAnnotation)


def _host_events(log_dir):
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((ev.name, dict(ev.stats)) for ev in line.events
                           if ev.name.startswith("fptc."))
    return out


def test_decode_to_host_spans_in_a_cpu_trace(tmp_path):
    c0, tables = uniform_code_container(24, seed=1)
    c1, _ = uniform_code_container(40, seed=2)
    dec = BatchDecoder(devices=None)
    dec.decode([c0, c1], tables).to_host()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        batch = dec.decode([c0, c1, c0], tables)
        out = batch.to_host()
    finally:
        jax.profiler.stop_trace()
    assert [len(y) for y in out] == [c0.signal_length, c1.signal_length,
                                     c0.signal_length]
    events = _host_events(str(tmp_path))
    names = {n for n, _ in events}
    assert set(HOST_SPANS) <= names
    d2h = [st for n, st in events if n == "fptc.drain.d2h"]
    assert d2h == [{"bytes": sum(int(g.nbytes)
                                 for g in batch.device_windows)}]
    dispatch = [st for n, st in events if n == "fptc.dispatch"]
    pads = list(dec.stats.bucket_pad)[-len(dispatch):]
    assert [(st["shard"], st["device"], st["words_padded"])
            for st in dispatch] == [(p["shard"], p["device"], p["words_padded"])
                                    for p in pads]


def test_decode_bucket_hlo_holds_the_phase_scopes():
    c, tables = uniform_code_container(16, seed=3)
    plan = BatchDecoder(devices=None).plan_for(c, tables)
    words = jax.ShapeDtypeStruct((64,), jnp.uint32)
    lowered = _decode_bucket.lower(
        words, words, jax.ShapeDtypeStruct((64,), jnp.int32),
        plan.tables, plan.lut, plan.rscale, None,
        l_max=plan.l_max, max_symlen=symlen_bucket(8), num_windows=64,
        n=plan.n, e=plan.e, use_kernels=False,
    )
    hlo = lowered.compile().as_text()
    for scope in DEVICE_SCOPES:
        assert f"/{scope}/" in hlo, scope
