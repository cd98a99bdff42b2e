#!/usr/bin/env python3
"""Smoke run of the FPTC archive path on a TPU.

Drives the system's main path once, through the entry points a user calls,
on an archive the size a server decoding sensor archives holds, and checks
every answer by the repository's own means:

  (a) device   the first device is a TPU; anything else exits non-zero
  (b) ingest   64 strips of the ten ``DATASETS`` (log-normal lengths, median
               2^20 samples, clipped to [2^18, 2^22], at least 64 Mi samples
               in all) encoded by ``BatchEncoder``; the shortest strip of
               each dataset, encoded again on the CPU device, must give the
               same container bytes
  (c) drain    ``BatchDecoder`` decodes the archive: every signal's PRD is
               bounded, and one container per dataset matches the host
               reference decoder (``core.codec.decode`` on the CPU)
  (d) migrate  ``Transcoder`` moves the archive to a cold config keeping half
               the coefficients, byte for byte against decode-to-host then
               re-encode
  (e) serve    the HTTP service (``launch/serve.py``) in-process on an
               ephemeral port: encode, decode, transcode and healthz answers
               byte for byte against the offline engines
  (f) kernels  the same signals and containers through ``use_kernels=True``
               engines, compiled by Mosaic (``tpu_custom_call``): bit-
               identical to (b) and (c); buckets past the kernels' VMEM
               limit raise ``KernelLimitError`` and are decoded split
  (g) kv       one ``KVCacheCodec`` fixed-rate round trip on a bf16
               ``[B, T, H, D]`` block shaped like a ``configs/`` model's
               cache, against the reference math on the CPU

Every device array lives on one chip.  ``--chips 4`` instead runs only the
sharded archive encode and decode (``devices="auto"`` over four chips)
against the same archive on one chip, byte for byte.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips

The lines before the last are smoke output (sizes, compile and run seconds,
peak device bytes), not benchmark metrics.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any
failed check raises, and the script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import sys
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The archive and request sizes one run uses."""

    strips: int = 64
    median: int = 1 << 20  # samples per strip, log-normal median
    lo: int = 1 << 18
    hi: int = 1 << 22
    min_total: int = 1 << 26  # samples in the whole archive
    sigma: float = 0.5
    serve_windows: int = 256  # windows per HTTP request signal
    kv_batch: int = 4
    kv_tokens: int = 512


FULL = Sizes()
# --chips 4 is a fault-finding check of the sharded path: every compile is
# paid once per chip, so it runs a smaller archive of the same shape.
SHARDED = Sizes(strips=16, median=1 << 18, lo=1 << 16, hi=1 << 20,
                min_total=1 << 22)

# Worst PRD (%) any strip may reconstruct with: the generators reach at
# most about 8% under their domain's tables, and a corrupt decode lands
# near 100%.
PRD_MAX = 20.0
# The configs/ model whose KV cache shape (heads H, head dim D) phase (g)
# compresses.
KV_ARCH = "qwen15_4b"


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# ---------------------------------------------------------------------------
# Phase bookkeeping.
# ---------------------------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Clock:
    """Splits each phase's wall time into compile seconds (XLA's backend
    compiles, or their loads from the persistent cache) and the rest."""

    def __init__(self):
        self.compile_s = 0.0
        self._lock = threading.Lock()

    def listen(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.compile_s += duration

    def phase(self, name: str, fn, *args, **kw):
        import jax

        t0, c0 = time.perf_counter(), self.compile_s
        out, info = fn(*args, **kw)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        stats = jax.devices()[0].memory_stats() or {}
        info = dict(info, compile_s=comp, run_s=wall - comp,
                    peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        print(f"[smoke] {name}: " + json.dumps(info, default=str), flush=True)
        return out


@dataclasses.dataclass
class Context:
    """What every phase shares: where arrays live and what must hold."""

    devices: Any  # the engines' ``devices`` argument
    cpu: Any  # the CPU device the reference runs on
    seed: int = 0
    mosaic: bool = True  # kernels must be compiled by Mosaic


@dataclasses.dataclass
class Archive:
    names: List[str]
    domain_ids: List[int]
    signals: List[np.ndarray]
    tables: Dict[int, Any]
    containers: List[Any] = dataclasses.field(default_factory=list)
    decoded: List[np.ndarray] = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------
def phase_device(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SmokeError(
            f"chip_smoke needs a TPU; JAX's first device is {platform!r} "
            f"({devs[0].device_kind}) — there is no CPU fallback"
        )
    if len(devs) < chips:
        raise SmokeError(f"--chips {chips} but JAX sees {len(devs)} devices")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# (b) ingest
# ---------------------------------------------------------------------------
def strip_lengths(sizes: Sizes, seed: int) -> np.ndarray:
    """Log-normal strip lengths (median ``sizes.median``) clipped to
    ``[lo, hi]``, scaled up (and clipped again) until they reach
    ``min_total`` samples."""
    rng = np.random.default_rng(seed)
    raw = sizes.median * np.exp(sizes.sigma * rng.standard_normal(sizes.strips))
    lengths = np.clip(raw, sizes.lo, sizes.hi)
    while lengths.sum() < sizes.min_total:
        lengths = np.clip(lengths * (sizes.min_total / lengths.sum()) * 1.001,
                          sizes.lo, sizes.hi)
    return np.ceil(lengths).astype(np.int64)


def domain_tables(seed: int) -> Dict[int, Any]:
    """One calibrated table set per paper domain, on 2^16 samples of each
    of the domain's datasets (domain_id = position in DOMAIN_DATASETS)."""
    from repro.core.calibration import calibrate
    from repro.core.config import DOMAIN_DEFAULTS
    from repro.data.signals import DATASETS, make_signal
    from repro.serving.traffic import DOMAIN_DATASETS

    tables = {}
    for d, (dom, _) in enumerate(DOMAIN_DATASETS):
        calib = [
            make_signal(nm, 1 << 16, seed=seed + 1000 + 10 * d + j)
            for j, nm in enumerate(sorted(DATASETS))
            if DATASETS[nm][0] == dom
        ]
        tables[d] = calibrate(
            np.concatenate(calib), DOMAIN_DEFAULTS[dom], domain_id=d
        )
    return tables


def make_archive(sizes: Sizes, seed: int) -> Archive:
    from repro.data.signals import DATASETS, make_signal
    from repro.serving.traffic import DOMAIN_DATASETS

    domain_id = {dom: i for i, (dom, _) in enumerate(DOMAIN_DATASETS)}
    names = [sorted(DATASETS)[i % len(DATASETS)] for i in range(sizes.strips)]
    lengths = strip_lengths(sizes, seed)
    signals = [
        make_signal(name, int(n), seed=seed * 1000 + i)
        for i, (name, n) in enumerate(zip(names, lengths))
    ]
    return Archive(
        names=names,
        domain_ids=[domain_id[DATASETS[nm][0]] for nm in names],
        signals=signals,
        tables=domain_tables(seed),
    )


def on_cpu(ctx: Context, tables: Dict[int, Any]) -> Dict[int, Any]:
    """Copies of ``tables`` whose device arrays live on the CPU."""
    import jax

    return {
        d: dataclasses.replace(t, quant=jax.device_put(t.quant, ctx.cpu))
        for d, t in tables.items()
    }


def shortest_per_dataset(arc: Archive) -> List[int]:
    best: Dict[str, int] = {}
    for i, nm in enumerate(arc.names):
        if nm not in best or len(arc.signals[i]) < len(arc.signals[best[nm]]):
            best[nm] = i
    return [best[nm] for nm in sorted(best)]


def phase_ingest(ctx: Context, arc: Archive):
    import jax

    from repro.serving import BatchEncoder

    enc = BatchEncoder(devices=ctx.devices)
    arc.containers = enc.encode(
        arc.signals, arc.tables, domain_ids=arc.domain_ids
    ).to_host()
    check(len(arc.containers) == len(arc.signals), "ingest lost containers")

    # the same strips, encoded again with every array on the CPU
    subset = shortest_per_dataset(arc)
    with jax.default_device(ctx.cpu):
        cpu_tables = on_cpu(ctx, arc.tables)
        ref = BatchEncoder(devices=[ctx.cpu], pipeline=False).encode(
            [arc.signals[i] for i in subset], cpu_tables,
            domain_ids=[arc.domain_ids[i] for i in subset],
        ).to_host()
    for i, c in zip(subset, ref):
        check(c.to_bytes() == arc.containers[i].to_bytes(),
              f"strip {i} ({arc.names[i]}): chip bytes differ from CPU bytes")
    samples = sum(len(s) for s in arc.signals)
    payload = sum(len(c.to_bytes()) for c in arc.containers)
    return arc.containers, {
        "strips": len(arc.signals), "samples": samples,
        "container_bytes": payload, "ratio": 4 * samples / payload,
        "cpu_identical_strips": len(subset),
    }


# ---------------------------------------------------------------------------
# (c) drain
# ---------------------------------------------------------------------------
def phase_drain(ctx: Context, arc: Archive, min_bytes: int):
    import jax

    from repro.core import codec
    from repro.core.metrics import prd
    from repro.serving import BatchDecoder

    dec = BatchDecoder(devices=ctx.devices)
    arc.decoded = dec.decode(arc.containers, arc.tables).to_host()
    nbytes = sum(x.nbytes for x in arc.decoded)
    check(nbytes >= min_bytes, f"decoded {nbytes} B < {min_bytes} B")
    worst: Dict[str, float] = {}
    for i, (src, out) in enumerate(zip(arc.signals, arc.decoded)):
        check(out.shape == src.shape and np.isfinite(out).all(),
              f"strip {i}: bad decode shape/values")
        p = prd(src, out)
        worst[arc.names[i]] = max(worst.get(arc.names[i], 0.0), p)
        check(p <= PRD_MAX, f"strip {i} ({arc.names[i]}): PRD {p:.3f}% > "
              f"{PRD_MAX}%")

    # the tolerance is zero: both decoders select coefficients from the
    # same host-computed LUT and split the iDCT into exact integer products
    subset = shortest_per_dataset(arc)
    with jax.default_device(ctx.cpu):
        cpu_tables = on_cpu(ctx, arc.tables)
        for i in subset:
            c = arc.containers[i]
            ref = codec.decode(c, cpu_tables[c.domain_id])
            check(np.array_equal(arc.decoded[i], ref),
                  f"strip {i}: engine decode differs from the host decoder")
    return arc.decoded, {
        "decoded_bytes": nbytes, "worst_prd_pct": worst,
        "host_ref_identical_containers": len(subset),
    }


# ---------------------------------------------------------------------------
# (d) migrate
# ---------------------------------------------------------------------------
def cold_tables(arc: Archive, seed: int) -> Dict[int, Any]:
    """Per domain, tables for the same window keeping half the
    coefficients (zone boundaries clipped to the new E)."""
    from repro.core.calibration import calibrate
    from repro.data.signals import make_signal
    from repro.serving.traffic import DOMAIN_DATASETS

    out = {}
    for d, (_, dataset) in enumerate(DOMAIN_DATASETS):
        cfg = arc.tables[d].config
        e = max(cfg.e // 2, 1)
        out[d] = calibrate(
            make_signal(dataset, 65536, seed=seed + 500 + d),
            cfg.replace(e=e, b1=min(cfg.b1, e), b2=min(cfg.b2, e)),
            domain_id=d,
        )
    return out


def phase_migrate(ctx: Context, arc: Archive):
    from repro.serving import BatchEncoder, Transcoder

    cold = cold_tables(arc, ctx.seed)
    tc = Transcoder(devices=ctx.devices)
    got = tc.transcode(arc.containers, arc.tables, cold).to_host()
    ref = BatchEncoder(devices=ctx.devices).encode(
        arc.decoded, cold, domain_ids=arc.domain_ids
    ).to_host()
    for i, (g, r) in enumerate(zip(got, ref)):
        check(g.to_bytes() == r.to_bytes(),
              f"strip {i}: transcode differs from decode + re-encode")
        check(g.e == cold[arc.domain_ids[i]].config.e, "wrong target E")
    return got, {
        "containers": len(got),
        "cold_bytes": sum(len(c.to_bytes()) for c in got),
        "transcoder_stats": dataclasses.asdict(tc.stats),
    }


# ---------------------------------------------------------------------------
# (e) serve
# ---------------------------------------------------------------------------
def _request(port: int, method: str, path: str, body: bytes = b""):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def phase_serve(ctx: Context, sizes: Sizes):
    from repro.core.container import Container
    from repro.data.signals import make_signal
    from repro.launch.serve import build_frontend, make_handler, parse_args
    from repro.serving import BatchDecoder, BatchEncoder, Transcoder
    from repro.serving.traffic import DOMAIN_DATASETS

    args = parse_args(["--seed", str(ctx.seed)])
    fe = build_frontend(args, devices=ctx.devices)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(fe))
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    port = httpd.server_port
    enc = BatchEncoder(pipeline=False, devices=ctx.devices)
    dec = BatchDecoder(pipeline=False, devices=ctx.devices)
    tr = Transcoder(encoder=enc, decoder=dec)
    answered = 0
    try:
        status, body = _request(port, "GET", "/healthz")
        check(status == 200 and json.loads(body)["status"] == "ok",
              f"/healthz answered {status} {body[:200]!r}")
        answered += 1
        for d, (_, dataset) in enumerate(DOMAIN_DATASETS):
            tab = fe.tables[d]
            sig = make_signal(dataset, sizes.serve_windows * tab.config.n,
                              seed=ctx.seed + 900 + d)
            status, blob = _request(port, "POST", f"/v1/encode?domain_id={d}",
                                    sig.astype("<f4").tobytes())
            check(status == 200, f"encode answered {status} {blob[:200]!r}")
            want = enc.encode([sig], tab).to_host()[0]
            check(blob == want.to_bytes(), f"domain {d}: encode bytes differ")

            status, raw = _request(port, "POST", "/v1/decode", blob)
            check(status == 200, f"decode answered {status} {raw[:200]!r}")
            ref = dec.decode([want], tab).to_host()[0]
            check(raw == ref.astype("<f4").tobytes(),
                  f"domain {d}: decode samples differ")

            dst = (d + 1) % len(DOMAIN_DATASETS)
            status, moved = _request(port, "POST", f"/v1/transcode?dst={dst}",
                                     blob)
            check(status == 200, f"transcode answered {status}")
            ref = tr.transcode_to_host(
                [Container.from_bytes(blob)], fe.tables, fe.tables,
                dst_domain_ids=[dst],
            )[0]
            check(moved == ref.to_bytes(), f"domain {d}: transcode differs")
            answered += 3
    finally:
        httpd.shutdown()
        httpd.server_close()
        fe.close(drain=True)
        server.join(timeout=60)
    check(not server.is_alive(), "HTTP server thread did not stop")
    return None, {"requests_answered": answered, "port": port}


# ---------------------------------------------------------------------------
# (f) kernels
# ---------------------------------------------------------------------------
def compiled_kernels_text(arc: Archive) -> str:
    """Compiled text of both megakernels as the engines call them, on the
    first container's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.core.quantize import quant_grid, recon_scale
    from repro.kernels import ops as kops
    from repro.serving.engine import p2, symlen_bucket

    c = arc.containers[0]
    tab = arc.tables[c.domain_id]
    cfg = tab.config
    wp, nwp = p2(c.num_words), p2(c.num_windows)
    u32 = jax.ShapeDtypeStruct((wp,), jnp.uint32)
    i32 = jax.ShapeDtypeStruct((wp,), jnp.int32)
    dev = tab.device_tables()
    lut, _ = quant_grid(tab.quant)
    dec = jax.jit(lambda hi, lo, sl: kops.decode_bucket_fused(
        hi, lo, sl, dev, lut, recon_scale(tab.quant), l_max=cfg.l_max,
        max_symlen=symlen_bucket(c.max_symlen), num_windows=nwp,
        n=cfg.n, e=cfg.e,
    ))
    sp = nwp * cfg.e
    enc = jax.jit(lambda s, v: kops.encode_bucket_fused(
        s, v, dev, chunk_size=min(1024, sp), l_max=cfg.l_max,
    ))
    return (
        dec.lower(u32, u32, i32).compile().as_text()
        + enc.lower(jax.ShapeDtypeStruct((2, sp), jnp.int32),
                    jax.ShapeDtypeStruct((2, sp), jnp.bool_))
        .compile().as_text()
    )


def _decode_split(dec, containers, tables, refused: List[int]):
    """Decode through the kernel engine; a bucket the kernels refuse is
    split in two until every part fits."""
    from repro.kernels.ops import KernelLimitError

    try:
        return dec.decode(containers, tables).to_host()
    except KernelLimitError:
        check(len(containers) > 1, "one container exceeds the kernel limit")
        refused.append(len(containers))
        half = len(containers) // 2
        return (_decode_split(dec, containers[:half], tables, refused)
                + _decode_split(dec, containers[half:], tables, refused))


def phase_kernels(ctx: Context, arc: Archive):
    from repro.serving import BatchDecoder, BatchEncoder

    text = compiled_kernels_text(arc)
    if ctx.mosaic:
        check("tpu_custom_call" in text,
              "the megakernels did not compile to a Mosaic custom call")
    enc = BatchEncoder(devices=ctx.devices, use_kernels=True)
    got = enc.encode(arc.signals, arc.tables,
                     domain_ids=arc.domain_ids).to_host()
    for i, (g, c) in enumerate(zip(got, arc.containers)):
        check(g.to_bytes() == c.to_bytes(),
              f"strip {i}: kernel encode differs from the XLA encode")
    dec = BatchDecoder(devices=ctx.devices, use_kernels=True)
    refused: List[int] = []
    by_domain: Dict[int, List[int]] = {}
    for i, d in enumerate(arc.domain_ids):
        by_domain.setdefault(d, []).append(i)
    for d, idxs in sorted(by_domain.items()):
        outs = _decode_split(dec, [arc.containers[i] for i in idxs],
                             arc.tables, refused)
        for i, out in zip(idxs, outs):
            check(np.array_equal(out, arc.decoded[i]),
                  f"strip {i}: kernel decode differs from the XLA decode")
    return None, {
        "mosaic_custom_call": "tpu_custom_call" in text,
        "encoded_identical": len(got), "decoded_identical": len(arc.decoded),
        "buckets_refused_and_split": refused,
    }


# ---------------------------------------------------------------------------
# (g) kv
# ---------------------------------------------------------------------------
def phase_kv(ctx: Context, sizes: Sizes):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.core import dct
    from repro.core.quantize import dequantize, quantize, recon_scale
    from repro.serving import BatchDecoder, BatchEncoder
    from repro.serving.workloads import KVCacheCodec

    arch = get_arch(KV_ARCH)
    h, d = arch.num_kv_heads, arch.head_dim
    rng = np.random.default_rng(ctx.seed + 77)
    kv_np = rng.standard_normal((sizes.kv_batch, sizes.kv_tokens, h, d))
    kv = jnp.asarray(kv_np, jnp.bfloat16)
    codec = KVCacheCodec(encoder=BatchEncoder(devices=ctx.devices),
                         decoder=BatchDecoder(devices=ctx.devices))
    tables = codec.calibrate(kv)
    ckv = codec.compress(kv)
    out = np.asarray(codec.decompress(ckv).astype(jnp.float32))
    levels = np.asarray(ckv.levels)

    with jax.default_device(ctx.cpu):
        quant = jax.device_put(tables.quant, ctx.cpu)
        n = tables.config.n
        x = jnp.moveaxis(jnp.asarray(kv_np, jnp.bfloat16)
                         .astype(jnp.float32), 1, -1)  # [B, H, D, T]
        win = x.reshape(x.shape[:-1] + (-1, n))
        ref_levels = np.asarray(quantize(dct.forward_dct(win, tables.config.e),
                                         quant))
        rec = dct.inverse_dct(dequantize(jnp.asarray(ref_levels), quant), n,
                              scale=recon_scale(quant))
        rec = jnp.moveaxis(rec.reshape(x.shape), -1, 1)
        ref = np.asarray(rec.astype(jnp.bfloat16).astype(jnp.float32))
    check(np.array_equal(levels, ref_levels),
          "KV levels differ from the reference quantizer")
    check(np.array_equal(out, ref),
          "KV round trip differs from the reference math")
    err = float(np.sqrt(np.mean((out - kv_np) ** 2)) / np.sqrt(np.mean(kv_np ** 2)))
    return None, {
        "arch": KV_ARCH, "shape": list(kv.shape), "ratio": ckv.ratio,
        "rel_rmse": err,
    }


# ---------------------------------------------------------------------------
# --chips 4: sharded encode + decode against one chip
# ---------------------------------------------------------------------------
def phase_sharded(ctx: Context, arc: Archive, one_chip: Sequence[Any]):
    from repro.serving import BatchDecoder, BatchEncoder

    def run(devices):
        enc = BatchEncoder(devices=devices)
        cs = enc.encode(arc.signals, arc.tables,
                        domain_ids=arc.domain_ids).to_host()
        outs = BatchDecoder(devices=devices).decode(cs, arc.tables).to_host()
        return cs, outs

    many_c, many_x = run(ctx.devices)
    one_c, one_x = run(list(one_chip))
    for i in range(len(arc.signals)):
        check(many_c[i].to_bytes() == one_c[i].to_bytes(),
              f"strip {i}: sharded encode differs from one chip")
        check(np.array_equal(many_x[i], one_x[i]),
              f"strip {i}: sharded decode differs from one chip")
    return None, {"strips": len(arc.signals), "identical": True}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        device = phase_device(args.chips)
    except SmokeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = Clock()
    clock.listen()
    print(f"[smoke] device: {json.dumps(device)}; compile cache {cache}",
          flush=True)
    sizes = SHARDED if args.chips > 1 else FULL
    chips = jax.devices()[: args.chips]
    ctx = Context(devices=chips, cpu=jax.devices("cpu")[0], seed=args.seed)
    arc = clock.phase("archive", lambda: (
        make_archive(sizes, args.seed), {"strips": sizes.strips}))
    if args.chips > 1:
        clock.phase("sharded", phase_sharded,
                    dataclasses.replace(ctx, devices="auto"), arc, chips[:1])
    else:
        clock.phase("ingest", phase_ingest, ctx, arc)
        clock.phase("drain", phase_drain, ctx, arc, 256 << 20)
        clock.phase("migrate", phase_migrate, ctx, arc)
        clock.phase("serve", phase_serve, ctx, sizes)
        clock.phase("kernels", phase_kernels, ctx, arc)
        clock.phase("kv", phase_kv, ctx, sizes)
    print(f"[smoke] total compile_s={clock.compile_s}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
