"""Pallas TPU megakernel: single-dispatch bucket decode (paper §4.2 fused).

FPTC's decoder is "one massively parallel pass" in the paper, but the
serving engine's kernel path used to be three device programs stitched by
XLA — the Huffman tile, an XLA scatter compaction, and the iDCT kernel —
each paying an HBM round trip for the ``[max_symlen, W]`` padded tile.
This kernel is the single-dispatch shape: one ``pallas_call`` whose grid
has two *phases* (the coarse/fine fusion of Tian et al., "Revisiting
Huffman Coding", and cuSZ+'s fused gap-array design):

  phase 1 (steps ``0 .. num_word_blocks``): per word block, the arithmetic
    canonical Huffman decode fills a VMEM tile, a running scalar offset
    over the SMEM symlen sidecar (the exclusive prefix scan, carried across
    the sequential TPU grid in SMEM scratch) assigns output positions, and
    the cooperative word-major store compacts symbols into a lane-dense
    ``[rows, 128]`` VMEM *scratch* stream — the padded tile and the dense
    symbol stream never touch HBM;
  phase 2 (remaining steps): per window block, levels are read back out of
    the dense scratch, dequantized by *exact selection* from the plan's
    256-level reconstruction LUT (``repro.core.quantize.quant_grid``) and
    transformed by the backend-exact split iDCT (``repro.core.dct``) into
    the output block.

The window block works on the stream's own layout: a stream row of
``Lw = lcm(E, 128)`` lanes holds ``Lw / E`` whole windows, so a
block-diagonal basis ``[Lw, (Lw/E) * N]`` turns a block of stream rows
straight into output samples with no relayout (``Lw > 128`` rows are read
as ``Lw / 128`` strided 128-lane row sets).  The LUT is selected already
split (three 10-bit slice fields per int32 entry, tiled to the lane
pattern), and the six bf16 slice products are exact integers, so the
output is bit-identical to the XLA bucket arm — and to itself on every
backend.

VMEM per grid step (BLOCK_WORDS=1024, BLOCK_WINDOWS=256, N, E <= 128):
  word block in: hi/lo               2 * 1024 * 4 B (x2)    =   16 KiB
  tiles (slot- and word-major)       2 * 128 * 1024 * 4 B   =    1 MiB
  split LUT                          (Lw/128) * 256 * 128*4 = 128-384 KiB
  block-diagonal basis slices        3 * Lw * (Lw/E) * N * 2 B
                                     (192 KiB at E=32, 4.5 MiB at E=6)
  out window block                   4 B * BLOCK_WINDOWS * N (x2)
  dense symbol scratch               4 B * (num_windows * E + 2 rows)
The dense scratch scales with the bucket and is what limits it.  Compiled
for a TPU v5e (128 MiB of VMEM per core, the kernel's scoped limit raised
to ``ops.VMEM_LIMIT_BYTES`` = 96 MiB), buckets of up to about 24 Mi symbols
fit at every paper-domain width (N = 32, E = 6/8/16/32), a few MiB less
when the bucket has few word blocks.  ``repro.kernels.ops`` admits what
``ops.decode_vmem_bytes`` counts within an 88 MiB budget (about 22 Mi
symbols) and refuses a bigger bucket with a typed
:class:`~repro.kernels.ops.KernelLimitError`; paging the scratch through
HBM is future work.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dct
from repro.core.quantize import expand_coded_stream, unpredict_levels
from repro.kernels.huffman_decode import (
    BLOCK_WORDS,
    LANES,
    decode_block_to_dense,
    dense_rows,
    table_operands,
    table_specs,
    word_block,
    word_operands,
)

__all__ = [
    "decode_fused",
    "BLOCK_WINDOWS",
    "row_lanes",
    "window_align",
]

BLOCK_WINDOWS = 256
_SLICE_FIELD = 10  # bits per split-LUT slice field: values in [0, 512]

_TRIVIAL = (0, 0, False)  # no predictor, no zero planes: the v1/v2 stream


def row_lanes(e: int) -> int:
    """``Lw = lcm(E, 128)``: the narrowest stream row holding whole windows."""
    return LANES * e // math.gcd(e, LANES)


def window_align(e: int) -> int:
    """Window-block granularity: 8 stream rows of ``Lw`` lanes."""
    return 8 * row_lanes(e) // e


def _split_lut(lut, rscale, e: int):
    """The LUT split against ``rscale`` (exactly as ``dct.inverse_dct``
    splits the coefficients it selects), packed three 10-bit fields per
    int32 and tiled to the 128-lane pattern of each row phase:
    int32[Lw/128, 256, 128]."""
    _, inv = dct.pow2_above(jnp.asarray(rscale, jnp.float32))
    sl = dct.split3(lut, inv)  # 3 x f32[E, 256], integers in [-256, 256]
    packed = sum(
        (s.astype(jnp.int32) + 256) << (_SLICE_FIELD * i)
        for i, s in enumerate(sl)
    )  # [E, 256]
    lw = row_lanes(e)
    band = np.arange(lw) % e  # band of each lane of a stream row
    return packed.T[:, band].reshape(256, lw // LANES, LANES).transpose(
        1, 0, 2
    )


def _block_diag_basis(n: int, e: int):
    """bf16[3, Lw, (Lw/E) * N]: the iDCT basis slices repeated on the
    diagonal, one window per ``E`` lanes of a stream row."""
    b_sl, _ = dct.idct_basis_slices(n, e)
    eye = np.eye(row_lanes(e) // e, dtype=np.float32)
    return jnp.asarray(
        np.stack([np.kron(eye, b) for b in b_sl]), jnp.bfloat16
    )


def _fused_kernel(
    hi_ref,
    lo_ref,
    sl_ref,  # SMEM int32[BW]
    limit_ref,
    first_ref,
    rank_ref,
    syms_ref,
    lutp_ref,  # int32[T, 256, 128] split LUT per row phase
    bd_ref,  # bf16[3, Lw, (Lw/E) * N] block-diagonal basis slices
    factor_ref,  # SMEM f32[1]: basis scale x LUT split scale
    # remaining refs: [idx_ref, seg_ref] (v3 coding only), then
    #   out_ref   f32[rows_per_block, (Lw/E) * N]
    #   dense_ref VMEM scratch int32[rows, 128]: the dense symbol stream
    #   tile_ref  VMEM scratch int32[128, BLOCK_WORDS]
    #   tilet_ref VMEM scratch int32[BLOCK_WORDS, 128]
    #   base_ref  SMEM scratch int32[1]
    *refs,
    l_max: int,
    max_symlen: int,
    num_word_blocks: int,
    block_rows: int,
    e: int,
    coding=_TRIVIAL,
):
    if coding == _TRIVIAL:
        out_ref, dense_ref, tile_ref, tilet_ref, base_ref = refs
    else:
        (idx_ref, seg_ref, out_ref, dense_ref, tile_ref, tilet_ref,
         base_ref) = refs
    i = pl.program_id(0)
    phases = lutp_ref.shape[0]  # Lw / 128

    @pl.when(i == 0)
    def _init():
        base_ref[0] = 0
        dense_ref[...] = jnp.zeros(dense_ref.shape, dense_ref.dtype)
        tile_ref[...] = jnp.zeros(tile_ref.shape, tile_ref.dtype)

    @pl.when(i < num_word_blocks)
    def _decode_phase():
        base = base_ref[0]
        decoded = decode_block_to_dense(
            hi_ref[...], lo_ref[...], sl_ref, limit_ref, first_ref,
            rank_ref, syms_ref[...], dense_ref, tile_ref, tilet_ref, base,
            l_max=l_max, max_symlen=max_symlen,
        )
        base_ref[0] = base + decoded

    if coding != _TRIVIAL:
        pred_id, bands, _ = coding

        # the v3 epilogue: one extra step's worth of work at the phase
        # boundary, still inside the same pallas_call.  The dense coded
        # stream is expanded to the full level grid (idx: -1 = zero-plane
        # suppressed or bucket padding -> zero bin 128) and un-predicted
        # per window segment — the SAME reference inverse the host decoder
        # and the XLA bucket arm call, so all three stay bit-identical.
        # Runs exactly once, on the first window-phase step, before any
        # window block reads the scratch back.  Gathers and a cumsum:
        # interpret mode only (ops.py refuses v3 codings on the chip).
        @pl.when(i == num_word_blocks)
        def _recode_phase():
            dense = dense_ref[...].reshape(-1)
            grid = expand_coded_stream(dense, idx_ref[...])
            grid = grid.reshape(-1, e)  # [nwp, e]
            lvl = unpredict_levels(
                grid.astype(jnp.uint32), seg_ref[...], pred_id, bands
            ).astype(jnp.int32)
            flat = lvl.reshape(-1)
            spill = dense.shape[0] - flat.shape[0]
            flat = jnp.concatenate([flat, jnp.full((spill,), 128, jnp.int32)])
            dense_ref[...] = flat.reshape(dense_ref.shape)

    @pl.when(i >= num_word_blocks)
    def _idct_phase():
        row0 = pl.multiple_of((i - num_word_blocks) * block_rows * phases, 8)
        prods = {}
        for t in range(phases):
            rows = (
                pl.ds(row0, block_rows) if phases == 1
                else pl.ds(row0 + t, block_rows, phases)
            )
            levels = dense_ref[rows, :]

            def select(v, acc, t=t, levels=levels):
                return jnp.where(
                    levels == v, lutp_ref[t, pl.ds(v, 1), :], acc
                )

            packed = jax.lax.fori_loop(
                0, 256, select, jnp.zeros(levels.shape, jnp.int32)
            )
            c = [
                (
                    ((packed >> (_SLICE_FIELD * s)) & 1023) - 256
                ).astype(jnp.float32).astype(jnp.bfloat16)
                for s in range(3)
            ]
            for group in dct.SLICE_PAIRS:
                for a, b in group:
                    p = jnp.dot(
                        c[a], bd_ref[b, pl.ds(t * LANES, LANES), :],
                        preferred_element_type=jnp.float32,
                    )
                    prods[a, b] = p if t == 0 else prods[a, b] + p
        acc = dct.combine_slice_products(lambda a, b: prods[a, b])
        out_ref[...] = acc * factor_ref[0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "l_max",
        "max_symlen",
        "num_windows",
        "n",
        "e",
        "coding",
        "block_words",
        "block_windows",
        "vmem_limit_bytes",
        "interpret",
    ),
)
def decode_fused(
    hi: jnp.ndarray,  # uint32[W] (concatenated, zero-padded bucket words)
    lo: jnp.ndarray,  # uint32[W]
    symlen: jnp.ndarray,  # int32[W] (0 on padding words)
    dec_limit: jnp.ndarray,
    dec_first: jnp.ndarray,
    dec_rank: jnp.ndarray,
    dec_syms: jnp.ndarray,
    lut: jnp.ndarray,  # f32[E, 256] quant_grid LUT
    rscale: jnp.ndarray,  # f32[] recon_scale the split is taken against
    idx: jnp.ndarray = None,  # int32[num_windows * e] (v3 coding only)
    seg: jnp.ndarray = None,  # int32[num_windows] (v3 coding only)
    *,
    l_max: int,
    max_symlen: int,
    num_windows: int,
    n: int,
    e: int,
    coding=_TRIVIAL,
    block_words: int = BLOCK_WORDS,
    block_windows: int = BLOCK_WINDOWS,
    vmem_limit_bytes: int = None,
    interpret: bool,
) -> jnp.ndarray:
    """One ``pallas_call``: packed bucket words -> windows f32[num_windows, N].

    The whole decode bucket — Huffman + prefix-scan compaction + dequant +
    iDCT — in a single dispatch with no intermediate HBM tensor: the padded
    tile and the dense symbol stream live in VMEM scratch only.  Positions
    past the stream's true symbol total read as level 0 (zero-initialized
    scratch + re-zeroed spill, matching the XLA scatter's zero fill), so
    even padding windows come out bit-identical to the XLA bucket arm.

    ``block_windows`` rounds up to :func:`window_align` (whole stream rows
    per block).  A non-trivial ``coding`` (container v3) keeps the
    single-dispatch shape: the coded-stream expansion + un-prediction
    epilogue (``quantize.expand_coded_stream`` / ``unpredict_levels``) runs
    in-kernel on the first window-phase grid step.  ``idx``/``seg`` are the
    host-built expansion arrays (``symlen.v3_expand_index``); they are
    padded here to the kernel's window-block rounding (-1 / self-segments,
    which expand and un-predict to the zero bin 128 — exactly the XLA arm's
    padding semantics).
    """
    coding = tuple(coding)
    block_words = max(min(word_block(block_words, interpret), hi.shape[0]), 1)
    nwb, hi, lo, symlen = word_operands(hi, lo, symlen, block_words)
    align = window_align(e)
    block_windows = -(-min(block_windows, max(num_windows, 1)) // align) * align
    num_win_blocks = -(-num_windows // block_windows)
    nwp = num_win_blocks * block_windows
    lw = row_lanes(e)
    block_rows = block_windows * e // lw
    rows = dense_rows(nwp * e, max_symlen)
    kernel = functools.partial(
        _fused_kernel,
        l_max=l_max,
        max_symlen=max_symlen,
        num_word_blocks=nwb,
        block_rows=block_rows,
        e=e,
        coding=coding,
    )
    _, b_scale = dct.idct_basis_slices(n, e)
    s, _ = dct.pow2_above(jnp.asarray(rscale, jnp.float32))
    factor = jnp.reshape(s * b_scale, (1,))
    lutp = _split_lut(lut, rscale, e)
    bd = _block_diag_basis(n, e)

    def word_ix(i):
        return (0, jnp.minimum(i, nwb - 1))

    def whole(shape):
        return pl.BlockSpec(
            shape, lambda i: (0,) * len(shape),
            pipeline_mode=pl.Buffered(1),
        )

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((1, block_words), word_ix),
        pl.BlockSpec((1, block_words), word_ix),
        pl.BlockSpec(
            (block_words,), lambda i: (jnp.minimum(i, nwb - 1),),
            memory_space=pltpu.SMEM,
        ),
        *table_specs(),
        whole(lutp.shape),
        whole(bd.shape),
        smem,
    ]
    operands = [
        hi, lo, symlen,
        *table_operands(dec_limit, dec_first, dec_rank, dec_syms),
        lutp, bd, factor,
    ]
    if coding != _TRIVIAL:
        if idx is None or seg is None:
            raise ValueError(
                "v3-coded decode_fused needs the idx/seg expansion arrays "
                "(symlen.v3_expand_index)"
            )
        idx = jnp.asarray(idx, jnp.int32)
        seg = jnp.asarray(seg, jnp.int32)
        if idx.shape[0] < nwp * e:
            idx = jnp.pad(
                idx, (0, nwp * e - idx.shape[0]), constant_values=-1
            )
        if seg.shape[0] < nwp:
            seg = jnp.concatenate(
                [seg, jnp.arange(seg.shape[0], nwp, dtype=jnp.int32)]
            )
        in_specs += [whole((nwp * e,)), whole((nwp,))]
        operands += [idx, seg]

    width = lw // e * n
    out = pl.pallas_call(
        kernel,
        grid=(nwb + num_win_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_rows, width), lambda i: (jnp.maximum(i - nwb, 0), 0),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (nwp * e // lw, width), jnp.float32
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((LANES, block_words), jnp.int32),
            pltpu.VMEM((block_words, LANES), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(nwp, n)[:num_windows]
