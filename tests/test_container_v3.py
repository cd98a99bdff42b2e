"""Container-v3 coverage: the fused predictor + zero-plane coding stage.

Pins the ISSUE's acceptance criteria end to end:

  * the v3 re-coding primitives (predict/unpredict, zero-plane masks,
    expansion index) are exact inverses;
  * unknown container versions fail loudly, naming the version byte and
    the supported set;
  * the kernel-path v3 decode/encode buckets still lower to EXACTLY one
    ``pallas_call`` each (the coding stage fused as prologue/epilogue,
    never a second dispatch), bit-identical to the XLA arms;
  * device-resident v2 -> v3 archive upgrades are byte-identical to the
    host decode + re-encode round trip with zero device->host transfers,
    including streams landing exactly at the 255/256/257 word marks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _synth import uniform_code_container
from repro.core import calibrate, decode, encode, symlen
from repro.core.calibration import DomainTables
from repro.core.config import DOMAIN_DEFAULTS, PREDICTORS, CodecConfig
from repro.core.container import (
    _HDR,
    HEADER_BYTES,
    SUPPORTED_VERSIONS,
    Container,
)
from repro.core.quantize import (
    expand_coded_stream,
    predict_levels,
    unpredict_levels,
)
from repro.data import make_signal
from repro.serving import BatchDecoder, BatchEncoder, Transcoder

CODINGS = [
    dict(predictor="delta", predict_bands=2, zero_planes=True),
    dict(predictor="delta", predict_bands=1, zero_planes=False),
    dict(predictor="linear2", predict_bands=3, zero_planes=True),
    dict(predictor="none", predict_bands=0, zero_planes=True),
]


@pytest.fixture(scope="module")
def power_tables():
    return calibrate(
        make_signal("load_power", 32768, seed=11), DOMAIN_DEFAULTS["power"]
    )


def _retable(tables: DomainTables, **coding) -> DomainTables:
    """Same quant/book/domain, a different (v3) coding on the config."""
    return dataclasses.replace(
        tables, config=tables.config.replace(**coding)
    )


# ---------------------------------------------------------------------------
# Re-coding primitives
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pred", ["delta", "linear2"])
@pytest.mark.parametrize("bands", [1, 3, 8])
def test_predict_unpredict_roundtrip(pred, bands):
    rng = np.random.default_rng(3)
    levels = rng.integers(0, 256, (37, 8)).astype(np.uint8)
    pred_id = PREDICTORS[pred]
    grid = np.asarray(predict_levels(jnp.asarray(levels), pred_id, bands))
    # untouched high bands pass through verbatim
    np.testing.assert_array_equal(grid[:, bands:], levels[:, bands:])
    seg = jnp.zeros((37,), jnp.int32)  # one segment starting at window 0
    back = np.asarray(unpredict_levels(
        jnp.asarray(grid, jnp.uint32), seg, pred_id, bands
    ))
    np.testing.assert_array_equal(back.astype(np.uint8), levels)


def test_zero_plane_masks_and_expansion_are_inverse():
    rng = np.random.default_rng(5)
    e = 6
    grids = []
    for nw in [4, 9, 1]:
        g = rng.integers(0, 256, (nw, e)).astype(np.uint8)
        g[1 % nw, :] = 128  # an all-zero window row
        g[:, 2] = 128  # an all-zero coefficient column
        grids.append(g)
    members = []
    coded_all = []
    for g in grids:
        zrow, zcol = symlen.zero_plane_masks(g)
        assert zrow.any() and zcol.any()
        members.append((g.shape[0], zrow, zcol))
        coded_all.append(g[~zrow, :][:, ~zcol].ravel())
    dense = np.concatenate(coded_all).astype(np.int32)
    total = sum(g.shape[0] for g in grids) + 3  # 3 padding windows
    idx, seg = symlen.v3_expand_index(members, e, total_windows=total)
    out = np.asarray(
        expand_coded_stream(jnp.asarray(dense), jnp.asarray(idx))
    ).reshape(total, e)
    np.testing.assert_array_equal(
        out[: sum(g.shape[0] for g in grids)],
        np.concatenate(grids).astype(np.int32),
    )
    # padding windows expand to the zero bin and are their own segments
    np.testing.assert_array_equal(out[-3:], 128)
    np.testing.assert_array_equal(
        seg[-3:], np.arange(total - 3, total, dtype=np.int32)
    )


# ---------------------------------------------------------------------------
# Versioning
# ---------------------------------------------------------------------------
def test_unknown_version_error_names_byte_and_supported_set(power_tables):
    """Satellite regression: an unreadable version byte must be NAMED in
    the error together with the supported set — not a bare magic/parse
    failure three layers down."""
    blob = bytearray(
        encode(make_signal("load_power", 2048, seed=6), power_tables)
        .to_bytes()
    )
    (magic, _version, *rest) = _HDR.unpack_from(bytes(blob), 0)
    for bad in (0, 4, 7, 255):
        blob[:HEADER_BYTES] = _HDR.pack(magic, bad, *rest)
        with pytest.raises(ValueError) as exc:
            Container.from_bytes(bytes(blob))
        assert f"version {bad}" in str(exc.value)
        assert str(SUPPORTED_VERSIONS) in str(exc.value)
    assert SUPPORTED_VERSIONS == (1, 2, 3)


def test_v3_reserved_flag_bits_rejected(power_tables):
    t3 = _retable(power_tables, **CODINGS[0])
    c = encode(make_signal("load_power", 2048, seed=6), t3)
    assert c.version == 3
    blob = bytearray(c.to_bytes())
    blob[HEADER_BYTES] |= 0x40  # a reserved flag bit inside _EXT3
    with pytest.raises(ValueError, match="reserved flag"):
        Container.from_bytes(bytes(blob))


# ---------------------------------------------------------------------------
# Fused kernels: still one pallas_call, still bit-identical
# ---------------------------------------------------------------------------
def _v3_bucket_operands(tables, seed=3):
    """One v3 decode bucket (p2-padded) + its plan and expansion arrays."""
    from repro.serving.batch_decode import _build_decode_plan
    from repro.core.symlen import words_to_u32
    from repro.serving.engine import p2, symlen_bucket

    c = encode(make_signal("load_power", 6000, seed=seed), tables)
    assert c.version == 3
    plan = _build_decode_plan(tables, c.plan_key, None)
    wp, nwp = p2(c.num_words), p2(c.num_windows)
    hi, lo = words_to_u32(c.words)
    hi2 = np.zeros(wp, np.uint32); hi2[: c.num_words] = hi
    lo2 = np.zeros(wp, np.uint32); lo2[: c.num_words] = lo
    sl2 = np.zeros(wp, np.int32); sl2[: c.num_words] = c.symlen
    idx, seg = symlen.v3_expand_index(
        [(c.num_windows, c.zrow, c.zcol)], c.e, total_windows=nwp
    )
    statics = dict(
        l_max=c.l_max, max_symlen=symlen_bucket(c.max_symlen),
        num_windows=nwp, n=c.n, e=c.e,
        coding=tables.config.coding,
    )
    return (
        plan, jnp.asarray(hi2), jnp.asarray(lo2), jnp.asarray(sl2),
        (jnp.asarray(idx), jnp.asarray(seg)), statics,
    )


@pytest.mark.parametrize("coding", CODINGS)
def test_v3_decode_bucket_is_one_pallas_call(power_tables, coding):
    """Acceptance: the v3 epilogue (expansion + un-prediction) fuses INTO
    the decode megakernel — still exactly one pallas_call, and the XLA arm
    stays pallas-free."""
    import functools

    from test_kernels import _count_eqns
    from repro.serving.batch_decode import _decode_bucket_math

    t3 = _retable(power_tables, **coding)
    plan, hi, lo, sl, v3, statics = _v3_bucket_operands(t3)
    fused = jax.make_jaxpr(functools.partial(
        _decode_bucket_math, use_kernels=True, **statics
    ))(hi, lo, sl, plan.tables, plan.lut, plan.rscale, v3)
    assert _count_eqns(fused.jaxpr, "pallas_call") == 1

    unfused = jax.make_jaxpr(functools.partial(
        _decode_bucket_math, use_kernels=False, **statics
    ))(hi, lo, sl, plan.tables, plan.lut, plan.rscale, v3)
    assert _count_eqns(unfused.jaxpr, "pallas_call") == 0


@pytest.mark.parametrize("coding", CODINGS)
def test_v3_decode_bucket_kernel_bit_identical(power_tables, coding):
    from repro.serving.batch_decode import _decode_bucket

    t3 = _retable(power_tables, **coding)
    plan, hi, lo, sl, v3, statics = _v3_bucket_operands(t3)
    ref = _decode_bucket(
        hi, lo, sl, plan.tables, plan.lut, plan.rscale, v3,
        use_kernels=False, **statics,
    )
    got = _decode_bucket(
        hi, lo, sl, plan.tables, plan.lut, plan.rscale, v3,
        use_kernels=True, **statics,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("coding", CODINGS)
def test_v3_encode_bucket_is_one_pallas_call(power_tables, coding):
    """Acceptance: the v3 prologue (prediction + zero-plane masks) fuses
    INTO the encode megakernel — still exactly one pallas_call."""
    import functools

    from test_kernels import _count_eqns
    from repro.serving.batch_encode import (
        _build_encode_plan,
        _encode_bucket_kernels_math,
    )

    t3 = _retable(power_tables, **coding)
    cfg = t3.config
    plan = _build_encode_plan(
        t3, (0, cfg.n, cfg.e, cfg.l_max, cfg.coding), None
    )
    x = jnp.zeros((2, 4 * cfg.n), jnp.float32)
    counts = jnp.zeros((2,), jnp.int32)
    traced = jax.make_jaxpr(functools.partial(
        _encode_bucket_kernels_math,
        n=cfg.n, e=cfg.e, l_max=cfg.l_max, chunk_size=64, check_gaps=True,
        coding=cfg.coding,
    ))(x, counts, plan.tables)
    assert _count_eqns(traced.jaxpr, "pallas_call") == 1


@pytest.mark.parametrize("coding", CODINGS)
def test_v3_encode_bucket_kernel_bit_identical(power_tables, coding):
    from repro.serving.batch_encode import (
        _build_encode_plan,
        _encode_bucket,
        _encode_bucket_kernels,
    )
    from repro.serving.engine import p2

    t3 = _retable(power_tables, **coding)
    cfg = t3.config
    n, e = cfg.n, cfg.e
    plan = _build_encode_plan(
        t3, (0, n, e, cfg.l_max, cfg.coding), None
    )
    sigs = [make_signal("load_power", L, seed=40 + i)
            for i, L in enumerate([1500, 700, 2048])]
    wp = p2(max(-(-s.shape[0] // n) for s in sigs))
    kp = p2(len(sigs))
    x = np.zeros((kp, wp * n), np.float32)
    counts = np.zeros((kp,), np.int32)
    for row, s in enumerate(sigs):
        x[row, : s.shape[0]] = s
        counts[row] = -(-s.shape[0] // n) * e
    for chunk in [64, wp * e]:
        ref = _encode_bucket(
            jnp.asarray(x), jnp.asarray(counts), plan.tables,
            n=n, e=e, chunk_size=chunk, check_gaps=False,
            coding=cfg.coding,
        )
        got = _encode_bucket_kernels(
            jnp.asarray(x), jnp.asarray(counts), plan.tables,
            n=n, e=e, l_max=cfg.l_max, chunk_size=chunk, check_gaps=False,
            coding=cfg.coding,
        )
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Engine round trips
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("coding", CODINGS)
def test_engine_v3_roundtrip_matches_host(power_tables, coding):
    """Both engine arms encode the exact host v3 bytes and decode them
    float-identically to the host decoder, across mixed lengths."""
    t3 = _retable(power_tables, **coding)
    sigs = [make_signal("load_power", L, seed=70 + i).astype(np.float32)
            for i, L in enumerate([5000, 777, 63])]
    host = [encode(s, t3) for s in sigs]
    for uk in (False, True):
        outs = BatchEncoder(chunk_size=None, use_kernels=uk).encode(
            sigs, t3
        ).to_host()
        for h, o in zip(host, outs):
            assert h.to_bytes() == o.to_bytes()
        parsed = [Container.from_bytes(h.to_bytes()) for h in host]
        recons = BatchDecoder(use_kernels=uk).decode(parsed, t3).to_host()
        for c, r in zip(parsed, recons):
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(decode(c, t3))
            )


# ---------------------------------------------------------------------------
# Device-resident v2 -> v3 archive upgrade
# ---------------------------------------------------------------------------
def test_v2_to_v3_transcode_byte_identity_zero_transfers(power_tables):
    """Satellite acceptance: upgrading a v2 archive to v3 on device is
    byte-identical to host decode + re-encode, with the decode -> re-encode
    stretch pinned transfer-free."""
    t2 = power_tables
    t3 = _retable(power_tables, **CODINGS[0])
    containers = [
        encode(make_signal("load_power", L, seed=80 + i), t2)
        for i, L in enumerate([6000, 1234, 257])
    ]
    ref = [encode(np.asarray(decode(c, t2)), t3) for c in containers]
    tc = Transcoder(chunk_size=None)
    with jax.transfer_guard_device_to_host("disallow"):
        batch = tc.transcode(containers, t2, t3)
    got = batch.to_host()
    for r, o in zip(ref, got):
        assert o.version == 3
        assert r.to_bytes() == o.to_bytes()


@pytest.mark.parametrize("num_words", [255, 256, 257])
def test_v2_to_v3_transcode_word_boundaries(num_words):
    """Streams landing exactly at / straddling the 256-word mark upgrade
    byte-identically (the stitch capacity and decode staging boundaries)."""
    c, tables = uniform_code_container(num_words, seed=num_words)
    t3 = _retable(tables, **CODINGS[0])
    ref = encode(np.asarray(decode(c, tables)), t3)
    got = Transcoder(chunk_size=None).transcode_to_host([c], tables, t3)[0]
    assert got.version == 3
    assert ref.to_bytes() == got.to_bytes()


def test_v3_encoded_batch_source_refuses_device_transcode(power_tables):
    """A v3-coded EncodedBatch source would need a host sync to rebuild
    the decode expansion — the zero-transfer path refuses loudly and
    leaves the source drainable."""
    t3 = _retable(power_tables, **CODINGS[0])
    sigs = [make_signal("load_power", 3000, seed=90).astype(np.float32)]
    batch = BatchEncoder(chunk_size=64).encode(sigs, t3)
    with pytest.raises(NotImplementedError, match="v3-coded"):
        Transcoder().transcode(batch, t3, power_tables)
    assert len(batch.to_host()) == 1  # refusal did not consume the source
