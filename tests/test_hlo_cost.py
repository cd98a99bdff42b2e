"""The trip-count-aware HLO cost model vs known-FLOP programs, and its
pre-lowering twin ``analyze_jaxpr`` — the only analyzer that can see into
a ``pallas_call`` (opaque by the time it reaches HLO text)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import analyze_hlo, analyze_jaxpr


def _hlo(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_single_matmul_exact():
    x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    c = analyze_hlo(_hlo(lambda a, b: a @ b, x, w))
    assert c.flops == pytest.approx(2 * 256 * 512 * 128, rel=1e-6)


def test_scan_multiplies_trip_count():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a, b):
        def body(c, _):
            return c @ b, None
        out, _ = jax.lax.scan(body, a, None, length=7)
        return out

    c = analyze_hlo(_hlo(f, x, w))
    assert c.flops == pytest.approx(7 * 2 * 128 ** 3, rel=0.01)
    assert c.num_whiles == 1
    assert c.unknown_trip_whiles == 0


def test_nested_scans_multiply():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a, b):
        def outer(c, _):
            def inner(d, _):
                return d @ b, None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        out, _ = jax.lax.scan(outer, a, None, length=5)
        return out

    c = analyze_hlo(_hlo(f, x, w))
    assert c.flops == pytest.approx(15 * 2 * 64 ** 3, rel=0.01)


def test_batched_dot_flops():
    x = jax.ShapeDtypeStruct((8, 32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 64, 16), jnp.float32)
    c = analyze_hlo(_hlo(lambda a, b: jnp.einsum("bik,bkj->bij", a, b), x, w))
    assert c.flops == pytest.approx(2 * 8 * 32 * 64 * 16, rel=1e-6)


def test_bytes_lower_bounded_by_io():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    c = analyze_hlo(_hlo(lambda a: a * 2.0 + 1.0, x))
    # one fusion: read 4MB, write 4MB
    assert c.hbm_bytes >= 2 * 1024 * 1024 * 4
    assert c.hbm_bytes <= 4 * 1024 * 1024 * 4  # no pathological double count


# ---------------------------------------------------------------------------
# analyze_jaxpr: the pre-lowering twin.
# ---------------------------------------------------------------------------
def test_jaxpr_single_matmul_exact():
    a = jnp.zeros((256, 512), jnp.float32)
    b = jnp.zeros((512, 128), jnp.float32)
    c = analyze_jaxpr(lambda x, w: x @ w, a, b)
    assert c.flops == pytest.approx(2 * 256 * 512 * 128, rel=1e-6)
    assert c.pallas_calls == 0
    # boundary bytes: at least the two operands + the output, once
    io = 4 * (256 * 512 + 512 * 128 + 256 * 128)
    assert c.hbm_bytes >= io


def test_jaxpr_scan_multiplies_trip_count():
    a = jnp.zeros((128, 128), jnp.float32)
    b = jnp.zeros((128, 128), jnp.float32)

    def f(x, w):
        def body(carry, _):
            return carry @ w, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    c = analyze_jaxpr(f, a, b)
    assert c.flops == pytest.approx(7 * 2 * 128 ** 3, rel=0.01)
    assert c.num_whiles == 1
    assert c.unknown_trip_whiles == 0


def test_jaxpr_attributes_pallas_call_from_grid():
    """A pallas_call's cost comes from (body cost) x prod(grid) and the
    declared BlockSpec traffic — the exact model the cost-model seeding
    path relies on for the fused megakernels."""
    pl = pytest.importorskip("jax.experimental.pallas")

    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = a_ref[...] @ b_ref[...]

    def f(a, b):
        return pl.pallas_call(
            kernel,
            grid=(2,),
            in_specs=[
                pl.BlockSpec((64, 32), lambda i: (i, 0)),
                pl.BlockSpec((32, 16), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((64, 16), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((128, 16), jnp.float32),
            interpret=True,
        )(a, b)

    a = jnp.zeros((128, 32), jnp.float32)
    b = jnp.zeros((32, 16), jnp.float32)
    c = analyze_jaxpr(f, a, b)
    assert c.pallas_calls == 1
    # per grid step one 64x32 @ 32x16 matmul, two steps
    assert c.flops == pytest.approx(2 * (2 * 64 * 32 * 16), rel=1e-6)
    # block pipeline: 2 steps x (a + b + out block bytes), plus whole-jaxpr
    # I/O (a, b, out arrays once)
    blocks = 2 * 4 * (64 * 32 + 32 * 16 + 64 * 16)
    io = 4 * (128 * 32 + 32 * 16 + 128 * 16)
    assert c.hbm_bytes == pytest.approx(blocks + io, rel=1e-6)


def test_jaxpr_fused_decode_megakernel_is_one_dispatch():
    """The real consumer: the decode megakernel traces to exactly one
    pallas_call with nonzero attributed flops (the HLO parser can't see
    this — in interpret mode the kernel lowers to an unrelated while-nest).
    """
    from repro.core import DOMAIN_DEFAULTS, calibrate, codec
    from repro.core.quantize import quant_grid, recon_scale
    from repro.kernels import ops as kops
    from repro.serving.engine import symlen_bucket

    rng = np.random.default_rng(77)
    tables = calibrate(
        rng.standard_normal(4096).astype(np.float32),
        DOMAIN_DEFAULTS["default"],
    )
    cfg = tables.config
    sig = rng.standard_normal(16 * cfg.n).astype(np.float32)
    cont = codec.encode(sig, tables)
    hi, lo = cont.words_u32()
    ms = symlen_bucket(cont.max_symlen)
    dev = tables.device_tables()
    lut, _ = quant_grid(tables.quant)
    rscale = recon_scale(tables.quant)

    def run(hi, lo, sl):
        return kops.decode_bucket_fused(
            hi, lo, sl, dev, lut, rscale,
            l_max=cfg.l_max, max_symlen=ms,
            num_windows=cont.num_windows, n=cfg.n, e=cfg.e,
        )

    c = analyze_jaxpr(
        run,
        jnp.asarray(hi),
        jnp.asarray(lo),
        jnp.asarray(cont.symlen, jnp.int32),
    )
    assert c.pallas_calls == 1
    assert c.flops > 0
    assert c.hbm_bytes > 0
