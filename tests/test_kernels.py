"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True.

The fused-kernel section additionally pins the megakernel acceptance
criteria: a kernel-path decode bucket lowers to exactly ONE pallas_call
with no ``[max_symlen, W]`` intermediate (jaxpr inspection), and the fused
encode/decode paths are BIT-identical to the XLA engine paths."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dct as dctlib
from repro.core.huffman import build_codebook
from repro.core.quantize import build_quant_table, quant_grid, recon_scale
from repro.core.symlen import pack_symlen_np, words_to_u32
from repro.kernels import ref as kref
from repro.kernels.dct_quant import dct_quant
from repro.kernels.huffman_decode import (
    huffman_decode_dense,
    huffman_decode_padded,
)
from repro.kernels.idct_dequant import idct_dequant


def _quant_table(e, seed=0):
    rng = np.random.default_rng(seed)
    calib = rng.standard_normal((2048, e)) * np.linspace(2, 0.2, e)
    return build_quant_table(
        calib, b1=max(e // 4, 1), b2=max(e // 2, 1), mu=50.0, alpha1=0.004,
        percentile=99.9,
    )


@pytest.mark.parametrize("l_max", [8, 12])
@pytest.mark.parametrize("n_syms", [100, 4096, 7000])
def test_huffman_decode_kernel_vs_ref(l_max, n_syms):
    rng = np.random.default_rng(l_max * 1000 + n_syms)
    syms = np.clip(rng.zipf(1.4, n_syms), 0, 255).astype(np.uint8)
    freqs = np.bincount(syms, minlength=256).astype(np.int64) + 1
    book = build_codebook(freqs, l_max=l_max)
    stream = pack_symlen_np(syms, book)
    hi, lo = words_to_u32(stream.words)
    args = (
        jnp.asarray(hi), jnp.asarray(lo),
        jnp.asarray(book.limit_shifted[1:], jnp.uint32),
        jnp.asarray(book.first_code_shifted, jnp.uint32),
        jnp.asarray(book.rank_offset, jnp.int32),
        jnp.asarray(book.sorted_symbols, jnp.int32),
    )
    kw = dict(l_max=l_max, max_symlen=stream.max_symlen)
    out_kernel = huffman_decode_padded(
        *args, **kw, block_words=128, interpret=True
    )
    out_ref = kref.huffman_decode_padded_ref(*args, **kw)
    np.testing.assert_array_equal(np.asarray(out_kernel), np.asarray(out_ref))
    # compacted stream equals original symbols
    valid = []
    for w, sl in enumerate(stream.symlen):
        valid.append(np.asarray(out_kernel)[w, :sl])
    np.testing.assert_array_equal(
        np.concatenate(valid).astype(np.uint8), syms
    )


@pytest.mark.parametrize("n,e", [(8, 4), (32, 16), (32, 32), (64, 24),
                                 (128, 128)])
@pytest.mark.parametrize("w", [16, 300, 1024])
def test_idct_dequant_kernel_vs_ref(n, e, w):
    rng = np.random.default_rng(n * e + w)
    t = _quant_table(e)
    levels = rng.integers(0, 256, (w, e)).astype(np.int32)
    lut, _ = quant_grid(t)
    out_k = idct_dequant(
        jnp.asarray(levels), lut, recon_scale(t),
        n=n, block_windows=256, interpret=True,
    )
    out_r = kref.idct_dequant_ref(jnp.asarray(levels), t, n=n)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("n,e", [(8, 4), (32, 16), (64, 64)])
@pytest.mark.parametrize("w", [64, 777])
def test_dct_quant_kernel_vs_ref(n, e, w):
    rng = np.random.default_rng(n + e + w)
    t = _quant_table(e, seed=n)
    windows = rng.standard_normal((w, n)).astype(np.float32)
    basis = dctlib.dct_basis(n, e)
    out_k = dct_quant(
        jnp.asarray(windows), t, basis, e=e, block_windows=128,
        interpret=True,
    )
    out_r = kref.dct_quant_ref(jnp.asarray(windows), t, e=e)
    k, r = np.asarray(out_k), np.asarray(out_r)
    # rounding at cell boundaries may differ by 1 level for a tiny fraction
    diff = np.abs(k - r)
    assert (diff > 1).mean() == 0.0
    assert (diff == 1).mean() < 2e-3


def test_kernel_end_to_end_codec_path():
    """decode_device(use_kernels=True) == host reference decode."""
    from repro.core import DOMAIN_DEFAULTS, calibrate, decode, decode_device, encode
    from repro.data import make_signal

    sig = make_signal("temperature", 8192, seed=11)
    tables = calibrate(
        make_signal("temperature", 32768, seed=12),
        DOMAIN_DEFAULTS["meteorological"],
    )
    c = encode(sig, tables)
    ref_out = decode(c, tables)
    k_out = decode_device(c, tables, use_kernels=True)
    np.testing.assert_allclose(ref_out, k_out, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Fused megakernels: single-dispatch decode, kernel-parity fused encode.
# ---------------------------------------------------------------------------
def _stream(l_max, n_syms, seed=0, pad_words=23):
    rng = np.random.default_rng(seed + l_max * 1000 + n_syms)
    syms = np.clip(rng.zipf(1.4, n_syms), 0, 255).astype(np.uint8)
    freqs = np.bincount(syms, minlength=256).astype(np.int64) + 1
    book = build_codebook(freqs, l_max=l_max)
    stream = pack_symlen_np(syms, book)
    hi, lo = words_to_u32(stream.words)
    # trailing padding words (symlen == 0), as bucket concatenation adds
    hi = np.concatenate([hi, np.zeros(pad_words, np.uint32)])
    lo = np.concatenate([lo, np.zeros(pad_words, np.uint32)])
    sl = np.concatenate([stream.symlen, np.zeros(pad_words, np.int32)])
    return syms, book, stream, hi, lo, sl


@pytest.mark.parametrize("l_max,n_syms", [(8, 100), (12, 4096), (12, 7001)])
def test_huffman_decode_dense_fused_compaction(l_max, n_syms):
    """The dense kernel (in-kernel prefix scan + cooperative store) equals
    the staged oracle: tile kernel + compact_padded_scatter."""
    syms, book, stream, hi, lo, sl = _stream(l_max, n_syms)
    out = huffman_decode_dense(
        jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(sl),
        jnp.asarray(book.limit_shifted[1:], jnp.uint32),
        jnp.asarray(book.first_code_shifted, jnp.uint32),
        jnp.asarray(book.rank_offset, jnp.int32),
        jnp.asarray(book.sorted_symbols, jnp.int32),
        l_max=l_max, max_symlen=stream.max_symlen,
        num_symbols=n_syms, block_words=128, interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(out).astype(np.uint8), syms
    )


def _bucket_operands(seed=3):
    """One realistic decode bucket (p2-padded words/windows) + its plan."""
    from repro.core import DOMAIN_DEFAULTS, calibrate, encode
    from repro.data import make_signal
    from repro.serving.batch_decode import _build_decode_plan
    from repro.serving.engine import p2, symlen_bucket

    tables = calibrate(
        make_signal("load_power", 32768, seed=seed), DOMAIN_DEFAULTS["power"]
    )
    c = encode(make_signal("load_power", 6000, seed=seed + 1), tables)
    plan = _build_decode_plan(tables, c.plan_key, None)
    wp, nwp = p2(c.num_words), p2(c.num_windows)
    hi, lo = words_to_u32(c.words)
    hi2 = np.zeros(wp, np.uint32); hi2[:c.num_words] = hi
    lo2 = np.zeros(wp, np.uint32); lo2[:c.num_words] = lo
    sl2 = np.zeros(wp, np.int32); sl2[:c.num_words] = c.symlen
    statics = dict(
        l_max=c.l_max, max_symlen=symlen_bucket(c.max_symlen),
        num_windows=nwp, n=c.n, e=c.e,
    )
    return plan, jnp.asarray(hi2), jnp.asarray(lo2), jnp.asarray(sl2), statics


def test_decode_megakernel_bit_identical_to_xla_bucket():
    """The fused decode (ONE pallas_call: huffman + compaction + LUT
    dequant + iDCT) returns bit-identical windows to the XLA bucket arm."""
    from repro.serving.batch_decode import _decode_bucket

    plan, hi, lo, sl, statics = _bucket_operands()
    ref = _decode_bucket(
        hi, lo, sl, plan.tables, plan.lut, plan.rscale,
        use_kernels=False, **statics,
    )
    got = _decode_bucket(
        hi, lo, sl, plan.tables, plan.lut, plan.rscale,
        use_kernels=True, **statics,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _count_eqns(jaxpr, name):
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            total += 1
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                total += _count_eqns(inner, name)
    return total


def _all_avals(jaxpr, out):
    """Shapes of every inter-op tensor.  Deliberately does NOT recurse into
    pallas_call bodies: refs/scratch inside the kernel are VMEM-resident by
    construction — the assertion is about tensors BETWEEN device programs
    (the HBM round trips the fusion exists to remove)."""
    for eqn in jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(tuple(aval.shape))
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _all_avals(inner, out)
    return out


def test_decode_bucket_kernel_path_is_one_pallas_call():
    """Acceptance: the kernel-path decode bucket lowers to EXACTLY one
    pallas_call, and no jaxpr intermediate carries the ``[max_symlen, W]``
    padded-tile shape (the HBM round trip the fusion removes).  The XLA
    arm of the same bucket is pallas-free."""
    from repro.serving.batch_decode import _decode_bucket_math

    plan, hi, lo, sl, statics = _bucket_operands()
    fused = jax.make_jaxpr(functools.partial(
        _decode_bucket_math, use_kernels=True, **statics
    ))(hi, lo, sl, plan.tables, plan.lut, plan.rscale)
    assert _count_eqns(fused.jaxpr, "pallas_call") == 1

    w = int(hi.shape[0])
    ms = statics["max_symlen"]
    tile_shapes = {(ms, w), (w, ms)}
    seen = set(_all_avals(fused.jaxpr, []))
    assert not (seen & tile_shapes), (
        f"fused path materializes the padded tile: {seen & tile_shapes}"
    )

    unfused = jax.make_jaxpr(functools.partial(
        _decode_bucket_math, use_kernels=False, **statics
    ))(hi, lo, sl, plan.tables, plan.lut, plan.rscale)
    assert _count_eqns(unfused.jaxpr, "pallas_call") == 0


def test_encode_fused_kernel_bit_identical_to_xla_bucket():
    """The kernel encode arm (DCT + quantize, then codeword lookup + greedy
    pack + word materialization in one pallas_call) emits the exact chunk
    parts of the XLA engine path, across chunk sizes including exact
    mode."""
    from repro.core import DOMAIN_DEFAULTS, calibrate
    from repro.data import make_signal
    from repro.serving.batch_encode import (
        _build_encode_plan,
        _encode_bucket,
        _encode_bucket_kernels,
    )
    from repro.serving.engine import p2

    tables = calibrate(
        make_signal("temperature", 32768, seed=5),
        DOMAIN_DEFAULTS["meteorological"],
    )
    cfg = tables.config
    n, e = cfg.n, cfg.e
    key = (tables.domain_id, n, e, cfg.l_max)
    plan = _build_encode_plan(tables, key, None)
    sigs = [make_signal("temperature", L, seed=40 + i)
            for i, L in enumerate([1500, 700, 2048])]
    wp = p2(max(-(-s.shape[0] // n) for s in sigs))
    kp = p2(len(sigs))
    x = np.zeros((kp, wp * n), np.float32)
    counts = np.zeros((kp,), np.int32)
    for row, s in enumerate(sigs):
        x[row, : s.shape[0]] = s
        counts[row] = -(-s.shape[0] // n) * e
    for chunk in [64, 1024, wp * e]:
        ref = _encode_bucket(
            jnp.asarray(x), jnp.asarray(counts), plan.tables,
            n=n, e=e, chunk_size=chunk, check_gaps=False,
        )
        got = _encode_bucket_kernels(
            jnp.asarray(x), jnp.asarray(counts), plan.tables,
            n=n, e=e, l_max=cfg.l_max, chunk_size=chunk, check_gaps=False,
        )
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_encode_kernel_path_is_one_pallas_call():
    from repro.core import DOMAIN_DEFAULTS, calibrate
    from repro.data import make_signal
    from repro.serving.batch_encode import (
        _build_encode_plan,
        _encode_bucket_kernels_math,
    )

    tables = calibrate(
        make_signal("load_power", 16384, seed=9), DOMAIN_DEFAULTS["power"]
    )
    cfg = tables.config
    plan = _build_encode_plan(
        tables, (0, cfg.n, cfg.e, cfg.l_max), None
    )
    x = jnp.zeros((2, 4 * cfg.n), jnp.float32)
    counts = jnp.zeros((2,), jnp.int32)
    traced = jax.make_jaxpr(functools.partial(
        _encode_bucket_kernels_math,
        n=cfg.n, e=cfg.e, l_max=cfg.l_max, chunk_size=64, check_gaps=True,
    ))(x, counts, plan.tables)
    assert _count_eqns(traced.jaxpr, "pallas_call") == 1


def test_dct_quant_exact_arm_matches_reference():
    """dct_quant(exact=True) traces the reference split DCT and quantizer
    inside the tile: levels equal the XLA forward_dct+quantize bit for
    bit."""
    from repro.core.quantize import quantize

    rng = np.random.default_rng(17)
    n, e, w = 32, 16, 700
    t = _quant_table(e, seed=2)
    windows = rng.standard_normal((w, n)).astype(np.float32)
    basis = dctlib.dct_basis(n, e)
    out = dct_quant(
        jnp.asarray(windows), t, basis, e=e, block_windows=128,
        interpret=True, exact=True,
    )
    ref = jax.jit(
        lambda win: quantize(dctlib.forward_dct(win, e), t).astype(jnp.int32)
    )(jnp.asarray(windows))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# int32 offset guard: the 2^31-byte boundary must raise loudly.
# ---------------------------------------------------------------------------
def test_i32_offset_guard_at_2gb_boundary():
    from repro.core.calibration import DeviceTables
    from repro.core.quantize import QuantTable
    from repro.kernels import ops as kops

    i32_max = np.iinfo(np.int32).max
    # just under the mark (mock arithmetic only — nothing is allocated)
    kops.check_i32_offsets(i32_max - 64, 64)
    with pytest.raises(ValueError, match="int32 offset range"):
        kops.check_i32_offsets(i32_max - 63, 64)
    with pytest.raises(ValueError, match="int32 offset range"):
        kops.check_i32_offsets(2 ** 31, 0)  # the 2^31-byte mark itself

    # and through the real decode entry point, with mocked (abstract)
    # shapes via eval_shape — no 2 GiB buffers are ever allocated
    spec = functools.partial(jax.ShapeDtypeStruct)
    w = 1 << 26
    tables = DeviceTables(
        codes=spec((256,), jnp.uint32),
        lengths=spec((256,), jnp.int32),
        dec_limit=spec((12,), jnp.uint32),
        dec_first=spec((13,), jnp.uint32),
        dec_rank=spec((13,), jnp.int32),
        dec_syms=spec((256,), jnp.int32),
        quant=QuantTable(
            zone=spec((16,), jnp.int32),
            scale=spec((16,), jnp.float32),
            mu=spec((), jnp.float32),
            alpha1=spec((), jnp.float32),
            thresholds=spec((2, 16, 128), jnp.float32),
            grid=spec((16, 256), jnp.float32),
        ),
    )
    with pytest.raises(ValueError, match="int32 offset range"):
        jax.eval_shape(
            functools.partial(
                kops.huffman_decode,
                l_max=12, max_symlen=64, num_symbols=2 ** 31,
            ),
            spec((w,), jnp.uint32),
            spec((w,), jnp.uint32),
            spec((w,), jnp.int32),
            tables,
        )
