"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function computes exactly what its kernel computes, using only jnp /
core-library code — no pallas_call.  Kernel tests sweep shapes/dtypes and
assert_allclose against these.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import dct as _dct
from repro.core import symlen as _symlen
from repro.core.quantize import (
    QuantTable,
    dequantize,
    quantize,
    recon_scale,
)

__all__ = ["huffman_decode_padded_ref", "idct_dequant_ref", "dct_quant_ref"]


def huffman_decode_padded_ref(
    hi, lo, dec_limit, dec_first, dec_rank, dec_syms, *, l_max, max_symlen
):
    """Padded per-word decode tile [W, max_symlen] — no compaction."""
    import jax

    def slot_step(carry, _):
        cur_hi, cur_lo = carry
        prefix = _symlen._shr32(cur_hi, 32 - l_max)
        ge = prefix[None, :] >= dec_limit[:, None]
        length = 1 + jnp.sum(ge.astype(jnp.int32), axis=0)
        length = jnp.minimum(length, l_max)
        fcs = dec_first[length]
        rank = dec_rank[length] + (
            _symlen._shr32(prefix - fcs, l_max - length)
        ).astype(jnp.int32)
        rank = jnp.clip(rank, 0, 255)
        sym = dec_syms[rank].astype(jnp.int32)
        new_hi = _symlen._shl32(cur_hi, length) | _symlen._shr32(
            cur_lo, 32 - length
        )
        new_lo = _symlen._shl32(cur_lo, length)
        return (new_hi, new_lo), sym

    (_, _), padded = jax.lax.scan(
        slot_step, (hi, lo), None, length=max_symlen
    )
    return padded.T  # [W, max_symlen]


def idct_dequant_ref(levels, quant_table: QuantTable, *, n: int):
    """[W, E] uint8/int32 levels -> [W, N] reconstructed samples."""
    coeffs = dequantize(levels.astype(jnp.uint8), quant_table)
    return _dct.inverse_dct(coeffs, n, scale=recon_scale(quant_table))


def dct_quant_ref(windows, quant_table: QuantTable, *, e: int):
    """[W, N] samples -> [W, E] int32 quantized levels."""
    coeffs = _dct.forward_dct(windows, e)
    return quantize(coeffs, quant_table).astype(jnp.int32)
