"""Pallas TPU kernel: fused dequantization + inverse DCT (paper §4.2.2).

The paper's second "lossy" kernel fuses per-sample dequantization with the
inverse DCT because both have uniform work.  On TPU the natural realization
is stronger: the inverse DCT over a window is a linear map, so the whole
stage is a **matmul on the MXU** with the dequantization fused into its
prologue on the VPU:

    levels int32[W_blk, E]  --(exact LUT selection)-->  f32[W_blk, E]
    f32[W_blk, E] @ idct_basis[E, N]  --(MXU)-->  f32[W_blk, N]

Dequantization selects from the plan's 256-level reconstruction LUT
(``repro.core.quantize.quant_grid``) and the matmul is the backend-exact
split iDCT of ``repro.core.dct`` against the table's ``recon_scale`` (the
basis rides in as its three integer slices), so the samples are the same
bits as the XLA fixed-rate arm's on every backend.

BlockSpec tiling: the window axis is tiled by ``block_windows`` (default 256,
a multiple of the 8-sublane f32 tile); E and N are kept whole per block (both
<= 128 by Table 1, i.e. a single lane tile).  VMEM per block at the default:
in 256*128*4 = 128 KiB, LUT 128 KiB, basis slices 192 KiB, out 128 KiB.

The window axis carries no per-container structure, so the fixed-rate
decode (serving.batch_decode) feeds this kernel the *concatenated* window
tensor of a whole bucket — N containers, one grid sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dct as _dct

__all__ = ["idct_dequant"]

BLOCK_WINDOWS = 256


def _kernel(
    levels_ref,  # int32[BW, E]
    lut_ref,  # f32[256, E]: row v reconstructs level v in every bin
    basis_ref,  # f32[3, E, N] integer slices of the iDCT basis
    scale_ref,  # f32[1, 2]: (1 / split scale, split scale x basis scale)
    out_ref,  # f32[BW, N]
):
    levels = levels_ref[...]

    def select(v, acc):
        return jnp.where(levels == v, lut_ref[pl.ds(v, 1), :], acc)

    coeffs = jax.lax.fori_loop(
        0, 256, select, jnp.zeros(levels.shape, jnp.float32)
    )
    inv, factor = scale_ref[:, 0:1], scale_ref[:, 1:2]
    b = basis_ref[...]
    acc = _dct.split_dot(_dct.split3(coeffs, inv), (b[0], b[1], b[2]))
    out_ref[...] = acc * factor


@functools.partial(
    jax.jit, static_argnames=("n", "block_windows", "interpret")
)
def idct_dequant(
    levels: jnp.ndarray,  # int32/uint8 [W, E]
    lut: jnp.ndarray,  # f32[E, 256] quant_grid reconstruction LUT
    rscale: jnp.ndarray,  # f32[] recon_scale the split is taken against
    *,
    n: int,
    block_windows: int = BLOCK_WINDOWS,
    interpret: bool,
) -> jnp.ndarray:
    """Fused LUT dequant + inverse DCT: [W, E] levels -> [W, N] samples,
    bit-identical to ``dct.inverse_dct(lut[k, levels], n, scale=rscale)``."""
    w, e = levels.shape
    num_blocks = -(-w // block_windows)
    wp = num_blocks * block_windows
    levels = levels.astype(jnp.int32)
    if wp != w:
        levels = jnp.pad(levels, ((0, wp - w), (0, 0)))
    b_sl, b_scale = _dct.idct_basis_slices(n, e)
    s, inv = _dct.pow2_above(jnp.asarray(rscale, jnp.float32))
    scales = jnp.stack([inv, s * b_scale]).reshape(1, 2)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    out = pl.pallas_call(
        _kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((block_windows, e), lambda i: (i, 0)),
            whole((256, e)),
            whole((3, e, n)),
            whole((1, 2)),
        ],
        out_specs=pl.BlockSpec((block_windows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, n), jnp.float32),
        interpret=interpret,
    )(levels, jnp.asarray(lut, jnp.float32).T, jnp.asarray(b_sl), scales)
    return out[:w]
