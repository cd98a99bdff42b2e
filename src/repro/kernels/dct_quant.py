"""Pallas TPU kernel: fused forward DCT + 3-zone quantization.

The encoder-side mirror of ``idct_dequant``: windows are transformed on the
MXU and quantized on the VPU in one VMEM residency.  The paper runs encode on
embedded devices — this kernel exists for the *server-side* bulk-compression
paths the framework adds beyond the paper (checkpoint compression, gradient
compression calibration, KV-cache compression), where encode throughput on
the accelerator matters.

    f32[W_blk, N] @ dct_basis[N, E]  --(MXU)-->  coeffs f32[W_blk, E]
    coeffs --(3-zone quantize, elementwise)-->  levels int32[W_blk, E]

Two arms share the tile: the default runs one f32 matmul and inlines the
3-zone math (hand-written for the VPU; may differ from the reference by one
level at a cell boundary for a ~1e-3 fraction of samples), while
``exact=True`` traces the reference itself inside the kernel — the
backend-exact split DCT (``repro.core.dct``) and the threshold quantizer
(``repro.core.quantize.quantize``) — for bit parity with the XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dct as _dct
from repro.core.quantize import QuantTable, quantize_to

__all__ = ["dct_quant"]

BLOCK_WINDOWS = 256
_ZERO_BIN = 128.0


def _kernel(
    windows_ref,  # f32[BW, N]
    zone_ref,  # int32[E]
    scale_ref,  # f32[E]
    thr_ref,  # f32[2, E, 128] level thresholds
    basis_ref,  # f32[N, E] (approximate arm) / f32[3, N, E] slices (exact)
    mu_ref,  # f32[1]
    alpha1_ref,  # f32[1]
    out_ref,  # int32[BW, E]
    *,
    exact: bool = False,
    basis_scale: float = 1.0,
):
    if exact:
        x = windows_ref[...]
        s, inv = _dct.pow2_above(jnp.max(jnp.abs(x), axis=-1, keepdims=True))
        b = basis_ref[...]
        c = _dct.split_dot(_dct.split3(x, inv), (b[0], b[1], b[2]))
        c = c * basis_scale * s
        out_ref[...] = quantize_to(c, thr_ref[...]).astype(jnp.int32)
        return
    c = jnp.dot(
        windows_ref[...], basis_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [BW, E]
    zone = zone_ref[...]
    a = scale_ref[...]
    mu = mu_ref[0]
    alpha1 = alpha1_ref[0]
    sign_pos = c > 0

    # zone 0: mu-law companding (Eq. 2)
    x = jnp.minimum(jnp.abs(c) / a, 1.0)
    q01 = jnp.log1p(mu * x) / jnp.log1p(mu)
    lvl0 = jnp.where(
        sign_pos, 129.0 + jnp.round(q01 * 126.0), 127.0 - jnp.round(q01 * 127.0)
    )
    lvl0 = jnp.where(c == 0, _ZERO_BIN, lvl0)

    # zone 1: linear deadzone (Eq. 3)
    d1 = alpha1 * a
    denom = jnp.maximum(a - d1, 1e-12)
    c_clip = jnp.clip(c, -a, a)
    mag = jnp.abs(c_clip)
    lvl1 = jnp.where(
        c_clip > d1,
        129.0 + jnp.floor((c_clip - d1) / denom * 126.0 + 0.5),
        jnp.where(
            c_clip < -d1,
            127.0 - jnp.floor((mag - d1) / denom * 127.0 + 0.5),
            _ZERO_BIN,
        ),
    )

    lvl = jnp.where(
        zone[None, :] == 0,
        lvl0,
        jnp.where(zone[None, :] == 1, lvl1, jnp.full_like(c, _ZERO_BIN)),
    )
    out_ref[...] = jnp.clip(lvl, 0.0, 255.0).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("e", "block_windows", "interpret", "exact")
)
def dct_quant(
    windows: jnp.ndarray,  # f32[W, N]
    quant: QuantTable,
    basis: jnp.ndarray,  # f32[N, E] (dct_basis; approximate arm)
    *,
    e: int,
    block_windows: int = BLOCK_WINDOWS,
    interpret: bool,
    exact: bool = False,
) -> jnp.ndarray:
    """Fused forward DCT + 3-zone quantize: [W, N] samples -> [W, E] levels.

    ``exact=True`` selects the reference-parity arm (see the module
    docstring); it takes the basis slices from ``core.dct`` itself."""
    w, n = windows.shape
    num_blocks = -(-w // block_windows)
    wp = num_blocks * block_windows
    if wp != w:
        windows = jnp.pad(windows, ((0, wp - w), (0, 0)))
    basis_scale = 1.0
    if exact:
        b_sl, basis_scale = _dct.dct_basis_slices(n, e)
        basis = jnp.asarray(b_sl)

    def rep(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    out = pl.pallas_call(
        functools.partial(_kernel, exact=exact, basis_scale=basis_scale),
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((block_windows, n), lambda i: (i, 0)),
            rep((e,)),
            rep((e,)),
            rep((2, e, 128)),
            rep(basis.shape),
            rep((1,)),
            rep((1,)),
        ],
        out_specs=pl.BlockSpec((block_windows, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, e), jnp.int32),
        interpret=interpret,
    )(
        windows,
        quant.zone,
        quant.scale,
        quant.thresholds,
        basis,
        jnp.reshape(quant.mu.astype(jnp.float32), (1,)),
        jnp.reshape(quant.alpha1.astype(jnp.float32), (1,)),
    )
    return out[:w]
