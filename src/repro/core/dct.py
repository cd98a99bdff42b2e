"""Windowed DCT-II forward / DCT-III inverse transforms (paper §3.1, Eq. 1).

The paper's transform for a window of N samples:

    C[k] = (2/N) * sum_n x[n] * cos(pi/N * (n + 1/2) * k),   k = 0..N-1

with inverse

    x[n] = C[0]/2 + sum_{k>=1} C[k] * cos(pi/N * (n + 1/2) * k).

On TPU both directions are realized as matmuls against a precomputed basis so
they run on the MXU (the paper's GPU kernel evaluates cosines per sample; the
TPU-native formulation is a [windows, N] @ [N, E] contraction, so both
directions inherit MXU throughput). Bases are cached per (N, E, dtype).

**Backend-exact contractions.**  A plain f32 matmul rounds differently on
every backend (CPU FMA order, the TPU MXU's bf16 passes, Mosaic's own
passes), so coefficients near a quantizer boundary would flip levels and
the same signal would encode to different bytes on different machines.
The transforms therefore run as *split* matmuls (the Ozaki scheme): each
operand is scaled by a power of two and cut into three 8-bit integer
slices, every slice is exact in bfloat16, every slice product and every
partial sum of at most 128 of them is an integer below 2^24 — exact in f32
whatever the accumulation order — and the six significant slice products
are combined elementwise in one fixed order.  The result is the same bits
on the CPU, under XLA on the TPU and inside a Pallas kernel, at ~2^-24
relative accuracy (the truncation of the third slice).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "dct_basis",
    "idct_basis",
    "dct_basis_slices",
    "idct_basis_slices",
    "forward_dct",
    "inverse_dct",
    "pow2_above",
    "split3",
    "split_dot",
    "combine_slice_products",
    "SLICE_PAIRS",
    "window_signal",
    "unwindow_signal",
]

SLICE_BITS = 8
# slice-product pairs (i, j) with i + j <= 4 (1-based): the ones whose weight
# 2^-8(i+j) reaches the f32 mantissa; grouped by weight, fixed order
SLICE_PAIRS = (((0, 0),), ((0, 1), (1, 0)), ((0, 2), (1, 1), (2, 0)))


@functools.lru_cache(maxsize=64)
def _dct_basis_np(n: int, e: int) -> np.ndarray:
    """Forward DCT-II basis, shape [N, E]: C = x @ basis."""
    if not (1 <= e <= n):
        raise ValueError(f"retained coeffs E={e} must satisfy 1 <= E <= N={n}")
    samples = np.arange(n, dtype=np.float64)[:, None]  # n index
    freqs = np.arange(e, dtype=np.float64)[None, :]  # k index
    basis = (2.0 / n) * np.cos(np.pi / n * (samples + 0.5) * freqs)
    return basis  # [N, E]


@functools.lru_cache(maxsize=64)
def _idct_basis_np(n: int, e: int) -> np.ndarray:
    """Inverse (DCT-III) basis, shape [E, N]: x = C @ basis.

    Truncated reconstruction: coefficients k >= E are treated as zero
    (spectral truncation, paper §3.1).
    """
    samples = np.arange(n, dtype=np.float64)[None, :]
    freqs = np.arange(e, dtype=np.float64)[:, None]
    basis = np.cos(np.pi / n * (samples + 0.5) * freqs)
    basis[0, :] *= 0.5  # DC term halved in the inverse
    return basis  # [E, N]


def dct_basis(n: int, e: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(_dct_basis_np(n, e), dtype=dtype)


def idct_basis(n: int, e: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(_idct_basis_np(n, e), dtype=dtype)


def _split_np(basis: np.ndarray):
    """Host split of a float64 basis: (f32[3, ...] integer slices, scale)."""
    scale = 2.0 ** (np.floor(np.log2(np.abs(basis).max())) + 1)
    y = basis / scale * 2.0**SLICE_BITS
    out = []
    for _ in range(3):
        s = np.round(y)
        out.append(s)
        y = (y - s) * 2.0**SLICE_BITS
    return np.stack(out).astype(np.float32), float(scale)


@functools.lru_cache(maxsize=64)
def dct_basis_slices(n: int, e: int):
    """Forward basis as (f32[3, N, E] integer slices, power-of-two scale)."""
    return _split_np(_dct_basis_np(n, e))


@functools.lru_cache(maxsize=64)
def idct_basis_slices(n: int, e: int):
    """Inverse basis as (f32[3, E, N] integer slices, power-of-two scale)."""
    return _split_np(_idct_basis_np(n, e))


def pow2_above(m: jnp.ndarray):
    """(s, 1/s): the power of two just above ``m >= 0`` and its inverse.

    Read off the f32 exponent bits, so it is exact on every backend (a
    ``log2`` would round differently).  The exponent is clamped so both
    stay normal floats; ``m == 0`` gives a tiny scale and all-zero slices.
    """
    bits = jax.lax.bitcast_convert_type(m.astype(jnp.float32), jnp.int32)
    ex = jnp.clip(jax.lax.shift_right_logical(bits, 23) & 0xFF, 1, 252)
    s = jax.lax.bitcast_convert_type((ex + 1) << 23, jnp.float32)
    inv = jax.lax.bitcast_convert_type((253 - ex) << 23, jnp.float32)
    return s, inv


def split3(x: jnp.ndarray, inv_scale) -> tuple:
    """Three integer-valued f32 slices of ``x * inv_scale`` (|.| < 1):
    ``x * inv_scale ~= sum_i slice_i * 2^-8(i+1)``.  Every step (power-of-two
    products, round-to-even, the subtraction of a number's own rounding) is
    exact, so the slices are the same bits on every backend."""
    y = x * inv_scale * 2.0**SLICE_BITS
    out = []
    for _ in range(3):
        s = jnp.round(y)
        out.append(s)
        y = (y - s) * 2.0**SLICE_BITS
    return tuple(out)


def combine_slice_products(prod) -> jnp.ndarray:
    """Sum the significant slice products ``prod(i, j)`` (exact integer
    arrays) weighted ``2^-8(i+j+2)``, in the one fixed elementwise order
    every caller shares — the in-kernel iDCT included."""
    acc = None
    for group in reversed(SLICE_PAIRS):
        g = None
        for i, j in group:
            g = prod(i, j) if g is None else g + prod(i, j)
        acc = g if acc is None else acc * 2.0**-SLICE_BITS + g
    return acc * 2.0 ** (-2 * SLICE_BITS)


def split_dot(a_slices, b_slices) -> jnp.ndarray:
    """Backend-exact ``(sum_i a_i 2^-8(i+1)) @ (sum_j b_j 2^-8(j+1))``.

    ``a_slices``/``b_slices`` are 3-sequences of integer-valued arrays (from
    :func:`split3` / the ``*_basis_slices`` tables) with |values| <= 256 and
    a contraction axis of at most 128: each single-pass bf16 slice product
    is then exact, and the slice products are summed elementwise in a fixed
    order.  Multiply the result by both operands' scales."""
    return combine_slice_products(
        lambda i, j: jnp.matmul(
            a_slices[i].astype(jnp.bfloat16),
            b_slices[j].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    )


def window_signal(signal: jnp.ndarray, n: int) -> jnp.ndarray:
    """Partition a 1-D signal strip into non-overlapping windows [W, N].

    The tail is zero-padded to a whole window (decoder trims via sample count
    carried in the container header).
    """
    length = signal.shape[-1]
    num_windows = -(-length // n)
    pad = num_windows * n - length
    if pad:
        signal = jnp.pad(signal, [(0, 0)] * (signal.ndim - 1) + [(0, pad)])
    return signal.reshape(signal.shape[:-1] + (num_windows, n))


def unwindow_signal(windows: jnp.ndarray, length: int) -> jnp.ndarray:
    """Inverse of :func:`window_signal`: [..., W, N] -> [..., length]."""
    flat = windows.reshape(windows.shape[:-2] + (-1,))
    return flat[..., :length]


def forward_dct(windows: jnp.ndarray, e: int) -> jnp.ndarray:
    """[..., W, N] windows -> [..., W, E] retained DCT-II coefficients.

    Backend-exact (see the module docstring): each window is scaled by the
    power of two above its own peak before the split."""
    n = windows.shape[-1]
    b_sl, b_scale = dct_basis_slices(n, e)
    x = windows.astype(jnp.float32)
    s, inv = pow2_above(jnp.max(jnp.abs(x), axis=-1, keepdims=True))
    acc = split_dot(split3(x, inv), b_sl)
    return acc * b_scale * s


def inverse_dct(coeffs: jnp.ndarray, n: int, scale=None) -> jnp.ndarray:
    """[..., W, E] coefficients -> [..., W, N] reconstructed windows.

    Backend-exact.  ``scale`` is the power of two the coefficients are
    split against (:func:`repro.core.quantize.recon_scale` of the decoding
    quant table — what the engines' LUT path uses, so both agree bit for
    bit); None scales each window by its own peak."""
    e = coeffs.shape[-1]
    b_sl, b_scale = idct_basis_slices(n, e)
    c = coeffs.astype(jnp.float32)
    if scale is None:
        s, inv = pow2_above(jnp.max(jnp.abs(c), axis=-1, keepdims=True))
    else:
        s, inv = pow2_above(jnp.asarray(scale, jnp.float32))
    acc = split_dot(split3(c, inv), b_sl)
    return acc * b_scale * s
