"""Closed loop of whole-archive decodes: host containers to host samples.

Set-up generates the configuration's archive (in the seed's order),
calibrates it, encodes it on the chip and decodes it once to warm every
bucket shape.  The window then
repeats ``BatchDecoder.decode(archive).to_host()`` until ``--seconds`` is
up, finishing the pass in flight; the host waits for the device program
(``bench.wait``) before the drain (``bench.to_host``), so that each span
holds one layer.  The check decodes a seeded sample of strips (every
dataset's, and the longest) of one seeded pass with the reference codec and
compares samples (``decode_gap``) and the levels they imply
(``level_miss``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List

import numpy as np

from fptcbench import archive, work
from fptcbench import reference as ref
from fptcbench.harness import Verdict
from fptcbench.seeds import rng


@dataclasses.dataclass
class State:
    arc: archive.Archive
    decoder: Any
    sample: List[int]
    kept: Dict[int, np.ndarray]
    missing: int = 0
    passes: int = 0


def pass_work(arc: archive.Archive):
    return work.total(
        work.decode_work(c.num_words, c.num_windows, c.n, c.e)
        for c in arc.containers)


def setup(ctx) -> State:
    from repro.serving import BatchDecoder

    cfg = ctx.config
    arc = archive.build(cfg, ctx.cell.chips, ctx.seed, ctx.devices, ctx.spans)
    dec = BatchDecoder(devices=ctx.devices)
    with ctx.spans.span("bench.warmup"):
        dec.decode(arc.containers, arc.tables).to_host()
    return State(arc, dec, archive.sample_strips(
        arc, cfg["sizes"]["check_strips_per_dataset"], ctx.seed), {})


def window(ctx, st: State, run) -> None:
    arc, dec = st.arc, st.decoder
    pick = rng(ctx.seed, "pass")
    flops, nbytes = pass_work(arc)
    up0, d0 = dec.executor.stats.upload_s, dec.stats.dispatches
    t_end = time.perf_counter() + ctx.seconds
    decoded, pass_s = 0, []
    while True:
        t0 = time.perf_counter()
        with ctx.spans.span("bench.decode"):
            batch = dec.decode(arc.containers, arc.tables)
        with ctx.spans.span("bench.wait"):
            batch.block_until_ready()
        with ctx.spans.span("bench.to_host"):
            out = batch.to_host()
        st.passes += 1
        st.missing += len(arc.containers) - len(out)
        for y, n in zip(out, arc.lengths):
            if y.shape != (n,):
                st.missing += 1
            decoded += y.nbytes
        if pick.random() * st.passes < 1.0:  # a uniform pass, kept
            st.kept = {i: out[i] for i in st.sample if i < len(out)}
        run.add_work("decode", flops, nbytes)
        pass_s.append(time.perf_counter() - t0)
        if time.perf_counter() >= t_end:
            break
    fresh = dec.stats.dispatches - d0
    pads = list(dec.stats.bucket_pad)[-fresh:] if fresh else []
    run.counters.update(
        passes=st.passes, decoded_bytes=decoded,
        pass_s_min=min(pass_s), pass_s_max=max(pass_s),
        upload_s=dec.executor.stats.upload_s - up0,
        words_live=sum(p["words"] for p in pads),
        words_padded=sum(p["words_padded"] for p in pads),
    )


def check(ctx, st: State, run) -> Verdict:
    st.decoder = None  # the system's state is freed before the reference runs
    limits = ctx.cell.traffic["limits"]
    arc = st.arc
    gap, missing, misses, compared = 0.0, st.missing, 0, 0
    for i in st.sample:
        y = st.kept.get(i)
        if y is None or y.shape != (arc.lengths[i],):
            missing += 1
            continue
        t = arc.ref_tables[arc.domain_ids[i]]
        p, levels = ref.decode_levels(arc.blobs[i], t)
        gap = max(gap, ref.sample_gap(y, ref.reconstruct(levels, t, p.signal_length), t))
        m, c = ref.level_miss(y, levels, t)
        misses, compared = misses + m, compared + c
    miss = misses / max(compared, 1)
    checks = [("decode_gap", gap, limits["decode_gap"]),
              ("level_miss", miss, limits["level_miss"]),
              ("missing", float(missing), 0.0)]
    return Verdict(
        correct=(gap <= limits["decode_gap"] and miss <= limits["level_miss"]
                 and missing == 0),
        attempted=st.passes * len(arc.containers), failed=missing,
        checks=checks)
