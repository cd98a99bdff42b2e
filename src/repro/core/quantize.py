"""Hybrid three-zone quantization (paper §3.2, Eqs. 2-3).

The E retained DCT coefficient indices are partitioned into three contiguous
zones by boundaries B1, B2:

  zone 0  [0,  B1): mu-law companding — fine resolution near zero, coarse at
                    the extremes. q in [0,1] mapped to 8-bit levels with
                    positive -> 129..255, negative -> 0..127, zero -> 128.
  zone 1  [B1, B2): symmetric linear quantizer with a deadzone of width
                    d1 = alpha1 * A1 around zero (everything inside collapses
                    to the 128 bin).
  zone 2  [B2, E ): aggressive zeroing — every coefficient maps to bin 128.

Per-bin maxima A[k] are clipped percentiles over a representative calibration
set (paper §3.2.1); the whole mapping is table-driven so the encoder is a
single vectorized pass.  The "quantization table" of the paper (Fig. 4) is the
:class:`QuantTable` pytree below: zone id, per-bin scale, and the two scalars
(mu, alpha1).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "QuantTable",
    "build_quant_table",
    "quantize",
    "dequantize",
    "level_thresholds",
    "recon_scale",
    "predict_levels",
    "unpredict_levels",
    "expand_coded_stream",
]

_ZERO_BIN = 128.0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantTable:
    """Table-driven 3-zone quantizer parameters for one signal domain.

    Attributes:
      zone:  int32[E]  — zone id per retained coefficient index (0/1/2).
      scale: float32[E] — per-bin clipped-percentile maximum (A0 / A1).
      mu:    float32[] — companding strength (zone 0).
      alpha1: float32[] — deadzone ratio (zone 1).
      thresholds: float32[2, E, 128] — the level boundaries the quantizer
        compares against (:func:`level_thresholds`).
      grid: float32[E, 256] — the reconstruction value of every level in
        every bin (:func:`level_grid`), what dequantization selects from.

    ``thresholds`` and ``grid`` are derived from the four fields above on
    the host when not given, so they are the same bits on every backend.
    """

    zone: jnp.ndarray
    scale: jnp.ndarray
    mu: jnp.ndarray
    alpha1: jnp.ndarray
    thresholds: jnp.ndarray = None
    grid: jnp.ndarray = None

    def __post_init__(self):
        for name, derive in (
            ("thresholds", level_thresholds), ("grid", level_grid)
        ):
            if getattr(self, name) is None:
                object.__setattr__(self, name, jnp.asarray(derive(
                    np.asarray(self.zone), np.asarray(self.scale),
                    float(self.mu), float(self.alpha1),
                )))

    def tree_flatten(self):
        return (
            self.zone, self.scale, self.mu, self.alpha1, self.thresholds,
            self.grid,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def num_coeffs(self) -> int:
        return self.zone.shape[0]


def level_thresholds(
    zone: np.ndarray, scale: np.ndarray, mu: float, alpha1: float
) -> np.ndarray:
    """The quantizer's level boundaries, f32[2, E, 128], computed on the host.

    ``[0, k]`` holds the ascending coefficient values at which bin k's level
    steps up from 128 (``c >= t`` counts one step), ``[1, k]`` the same for
    ``-c`` stepping down; unreachable steps are +inf.  They are the exact
    real-valued boundaries of Eqs. 2-3 (mu-law rounding in zone 0, the
    deadzone linear quantizer in zone 1, clipping at A), evaluated in
    float64 and rounded *up* to f32, so the comparison ``c >= t`` in f32 is
    exact.  Comparisons are the same bits on every backend, unlike a
    ``log1p`` or a division, so a coefficient quantizes to the same level on
    the CPU, the TPU and inside a kernel.
    """
    zone = np.asarray(zone).ravel()
    a = np.asarray(scale, np.float32).ravel().astype(np.float64)
    e = zone.shape[0]
    thr = np.full((2, e, 128), np.inf)
    tiny = float(np.finfo(np.float32).tiny)  # c > 0 (normal floats)
    lmu = np.log1p(mu)
    steps = {0: np.arange(1, 127) - 0.5, 1: np.arange(1, 128) - 0.5}
    levels = {0: 126.0, 1: 127.0}
    for k in range(e):
        if zone[k] == 0:
            for side in (0, 1):
                thr[side, k, 0] = tiny
                q = steps[side] / levels[side]
                thr[side, k, 1:1 + q.size] = a[k] * np.expm1(q * lmu) / mu
        elif zone[k] == 1:
            d1 = float(np.float32(alpha1) * np.float32(a[k]))
            denom = max(a[k] - d1, 1e-12)
            for side in (0, 1):
                thr[side, k, 0] = np.nextafter(np.float32(d1), np.inf)
                q = steps[side] / levels[side]
                thr[side, k, 1:1 + q.size] = d1 + q * denom
        thr[:, k][thr[:, k] > a[k]] = np.inf  # |c| clips to A
    t32 = thr.astype(np.float32)
    low = t32.astype(np.float64) < thr
    t32[low] = np.nextafter(t32[low], np.float32(np.inf))
    return t32


def level_grid(
    zone: np.ndarray, scale: np.ndarray, mu: float, alpha1: float
) -> np.ndarray:
    """Every level's reconstruction per bin, f32[E, 256], on the host.

    The midpoint of each quantization cell (Eqs. 2-3 inverted: mu-law
    expansion in zone 0, the deadzone linear map in zone 1, zero in zone 2
    and on the zero bin), evaluated in float64 and rounded once to f32 —
    a ``expm1`` on the device would round differently per backend."""
    zone = np.asarray(zone).ravel()[:, None]
    a = np.asarray(scale, np.float32).ravel().astype(np.float64)[:, None]
    lvl = np.arange(256, dtype=np.float64)[None, :]
    pos, neg = lvl > _ZERO_BIN, lvl < _ZERO_BIN
    q = np.clip(np.where(pos, (lvl - 129.0) / 126.0, (127.0 - lvl) / 127.0),
                0.0, 1.0)
    mag0 = a * np.expm1(q * np.log1p(mu)) / mu
    d1 = (np.float32(alpha1) * a.astype(np.float32)).astype(np.float64)
    mag1 = d1 + q * (a - d1)
    sign = np.where(pos, 1.0, np.where(neg, -1.0, 0.0))
    grid = np.where(zone == 0, sign * mag0, np.where(zone == 1, sign * mag1,
                                                     0.0))
    return grid.astype(np.float32)


def build_quant_table(
    calib_coeffs: np.ndarray,
    *,
    b1: int,
    b2: int,
    mu: float,
    alpha1: float,
    percentile: float,
    scale_headroom: float = 1.0,
) -> QuantTable:
    """Build a :class:`QuantTable` from calibration coefficients [W, E].

    The per-bin scale is the ``percentile`` of |coeff| over calibration
    windows (paper: "clipped percentile ... rejecting outliers that would
    otherwise waste quantization levels on rare extremes").
    """
    calib_coeffs = np.asarray(calib_coeffs, dtype=np.float64)
    if calib_coeffs.ndim != 2:
        calib_coeffs = calib_coeffs.reshape(-1, calib_coeffs.shape[-1])
    e = calib_coeffs.shape[-1]
    if not (0 <= b1 <= b2 <= e):
        raise ValueError(f"need 0 <= B1({b1}) <= B2({b2}) <= E({e})")
    scale = np.percentile(np.abs(calib_coeffs), percentile, axis=0)
    # Headroom guards against clipping on non-stationary domains where the
    # deployed data's tails exceed the calibration percentile (paper §3.4.1:
    # A0 is set per-domain by stationarity; seismic needs the most slack).
    scale = np.maximum(scale * scale_headroom, 1e-12)
    zone = np.full((e,), 2, dtype=np.int32)
    zone[:b2] = 1
    zone[:b1] = 0
    return QuantTable(
        zone=jnp.asarray(zone),
        scale=jnp.asarray(scale, dtype=jnp.float32),
        mu=jnp.float32(mu),
        alpha1=jnp.float32(alpha1),
    )


def quantize(coeffs: jnp.ndarray, table: QuantTable) -> jnp.ndarray:
    """Map float coefficients [..., E] to uint8 levels via the 3-zone table.

    The level is ``128 + #(c >= up-steps) - #(-c >= down-steps)`` against
    the table's :func:`level_thresholds` — comparisons only, so the same
    coefficient gets the same level on every backend."""
    return quantize_to(coeffs, table.thresholds)


def quantize_to(coeffs: jnp.ndarray, thr: jnp.ndarray) -> jnp.ndarray:
    """:func:`quantize` against the thresholds alone (what a kernel holds)."""
    c = coeffs.astype(jnp.float32)[..., None]
    up = jnp.sum(c >= thr[0], axis=-1, dtype=jnp.int32)
    down = jnp.sum(-c >= thr[1], axis=-1, dtype=jnp.int32)
    return (128 + up - down).astype(jnp.uint8)


def dequantize(levels: jnp.ndarray, table: QuantTable) -> jnp.ndarray:
    """Inverse 3-zone mapping: uint8 levels [..., E] -> float32 coefficients.

    The midpoint reconstruction of each quantization cell, selected from
    the table's host-computed :func:`level_grid` (so the same bits on every
    backend)."""
    e = table.num_coeffs
    return table.grid[jnp.arange(e, dtype=jnp.int32), levels.astype(jnp.int32)]


# ---------------------------------------------------------------------------
# Container-v3 window prediction (ROADMAP item 3, cuSZ+-style delta coding).
#
# A lossless re-coding of the quantized levels BEFORE entropy coding: for
# the low-frequency bands k < predict_bands, the coded symbol is the mod-256
# residual of the level against a prediction from the previous window(s),
# with a virtual all-128 (zero-bin) history before the first window of each
# signal.  Smooth domains concentrate the residual histogram around 128,
# which the canonical Huffman stage then exploits.  This is the EXACT
# reference math: the XLA bucket arms, the Pallas megakernels (which trace
# these functions in-kernel) and the host codec all call these same
# functions, so fused == unfused stays bit-identical by construction.
#
# All arithmetic runs in uint32 mod 256 — safe because 256 divides 2**32,
# so uint32 wraparound never changes a value mod 256 (the linear2 inverse
# takes a double cumulative sum whose intermediates overflow u8/i32).
# ---------------------------------------------------------------------------
def predict_levels(
    levels: jnp.ndarray, pred_id: int, predict_bands: int
) -> jnp.ndarray:
    """Forward prediction: uint8 levels ``[..., W, E]`` -> coded grid.

    Columns ``k < predict_bands`` become mod-256 residuals against the
    predictor (``pred_id`` 1 = delta, 2 = linear2); the rest pass through.
    Purely row-local along the window axis (shift-with-128-fill), so it
    vmaps over batch rows with no segment bookkeeping: every leading-axis
    row is one signal.
    """
    if pred_id == 0 or predict_bands == 0:
        return levels
    l = levels.astype(jnp.uint32)
    zero = jnp.full_like(l[..., :1, :], 128)
    l1 = jnp.concatenate([zero, l[..., :-1, :]], axis=-2)  # prev window
    if pred_id == 1:
        pred = l1
    else:
        l2 = jnp.concatenate([zero, l1[..., :-1, :]], axis=-2)  # prev-prev
        pred = 2 * l1 - l2  # u32 wrap ok mod 256
    r = jnp.mod(l - pred + 128, 256)
    e = levels.shape[-1]
    band = jnp.arange(e, dtype=jnp.int32) < predict_bands
    return jnp.where(band, r, l).astype(jnp.uint8)


def _seg_cumsum(t: jnp.ndarray, seg_start: jnp.ndarray) -> jnp.ndarray:
    """Segmented inclusive cumsum along axis 0 of ``t`` [W, E] (uint32).

    ``seg_start[w]`` is the index of the first window of w's segment (self
    for single-window segments).  Implemented as a plain cumsum minus a
    gather of the exclusive cumsum at each segment start — no scan over
    segments, so it lowers to the same primitives inside and outside Pallas.
    """
    a = jnp.cumsum(t, axis=0, dtype=jnp.uint32)  # inclusive
    excl = a - t  # exclusive
    return a - excl[seg_start, :]


def unpredict_levels(
    grid: jnp.ndarray,
    seg_start: jnp.ndarray,
    pred_id: int,
    predict_bands: int,
) -> jnp.ndarray:
    """Inverse prediction: coded grid ``[W, E]`` (any uint dtype) -> levels.

    Exactly inverts :func:`predict_levels` over concatenated signals:
    ``seg_start`` marks each window's signal start so predictions never
    cross a signal boundary.  The delta inverse is one segmented cumsum of
    ``t = (r - 128) mod 256``; linear2 telescopes to a double segmented
    cumsum.  A window whose residuals are all ``t == 0`` (e.g. suppressed /
    padding windows expanded to 128) contributes the identity, which is why
    zero-plane expansion commutes with unprediction.
    """
    if pred_id == 0 or predict_bands == 0:
        return grid.astype(jnp.uint8)
    g = grid.astype(jnp.uint32)
    t = jnp.mod(g + 128, 256)  # (r - 128) mod 256
    cs = _seg_cumsum(t, seg_start)
    if pred_id == 2:
        cs = _seg_cumsum(cs, seg_start)
    lvl = jnp.mod(cs + 128, 256)
    e = grid.shape[-1]
    band = jnp.arange(e, dtype=jnp.int32) < predict_bands
    return jnp.where(band, lvl, g).astype(jnp.uint8)


def expand_coded_stream(
    dense: jnp.ndarray, idx: jnp.ndarray
) -> jnp.ndarray:
    """Zero-plane expansion: dense coded symbols -> flat residual grid.

    ``idx[p]`` is the position of flat grid cell ``p`` in the dense coded
    stream, or ``-1`` where the cell was suppressed (zero-plane) or is
    bucket padding — those cells expand to the zero bin 128.  ``idx`` is
    built host-side at staging time (:func:`repro.core.symlen.
    v3_expand_index`); the gather itself is shared by the XLA arm and the
    decode megakernel epilogue.
    """
    took = dense[jnp.clip(idx, 0, None)]
    return jnp.where(idx >= 0, took, jnp.asarray(128, dense.dtype))


def quant_grid(table: QuantTable) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All 256 reconstruction values per bin: ([E, 256] LUT, the levels).

    The dequantization table the engines and kernels select from (exact
    selection, gather-free in the kernels) and tests bound the per-cell
    quantization error with: the table's host-computed ``grid``.
    """
    return table.grid, jnp.arange(256, dtype=jnp.uint8)


def recon_scale(table: QuantTable) -> jnp.ndarray:
    """A bound on every reconstructed |coefficient| of ``table`` (the
    largest per-bin scale A): what the backend-exact inverse DCT splits the
    dequantized coefficients against (``dct.inverse_dct(..., scale=)``), so
    the host decoder, the engines' LUT path and the decode megakernel all
    cut the same slices."""
    return jnp.max(table.scale)
