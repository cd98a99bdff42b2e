"""Pallas TPU kernel: SymLen word-parallel Huffman decode (paper §4.2.1).

GPU original: one CUDA thread per 64-bit word, serial LUT loop per thread,
warp-shuffle cooperative writes.  TPU adaptation:

  * one VPU **lane** per word — a block of ``BLOCK_WORDS`` words is decoded by
    looping over *symbol slots*; every iteration decodes one symbol for all
    words in the block simultaneously (branch-free, no divergence possible);
  * the 2^L_max shared-memory LUT is replaced by **arithmetic canonical
    decoding**: length = 1 + #(prefix >= limit_shifted[l]) via compares
    against the SMEM table scalars, then rank arithmetic; the final 256-way
    symbol lookup is a **one-hot matmul** against the symbol table
    (gather-via-one-hot — the MXU idiom for small-table lookups; 0/1 and
    symbols < 256 are exact in one bf16 pass);
  * 64-bit words are processed as (hi, lo) 32-bit pairs with funnel shifts
    (TPU int64 is emulated); the kernels carry them as int32 and shift
    logically, since Mosaic lowers neither unsigned reductions nor
    unsigned-to-float casts;
  * the warp-cooperative coalesced write stage comes in two forms.  The
    *staged* kernel (:func:`huffman_decode_tile`) stores a dense **padded
    tile** ``[MAX_SYMS, BLOCK_WORDS]`` and leaves compaction (exclusive
    prefix-sum of symlen + scatter) to the XLA level — exactly the paper's
    prefix-scan, lifted out of the kernel.  The *fused* kernel
    (:func:`huffman_decode_dense`) brings that stage back inside the
    ``pallas_call``: the symlen sidecar rides into SMEM, a running scalar
    offset (the prefix scan, one word at a time) gives every word its
    output position, and a cooperative word-major store compacts the tile
    — which lives only in VMEM scratch — into a lane-dense ``[rows, 128]``
    symbol stream.  One dispatch, no ``[max_symlen, W]`` HBM round trip
    (the coarse/fine fusion of Tian et al., "Revisiting Huffman Coding").

VMEM budget per block (BLOCK_WORDS=1024, L_max<=16):
  in:  hi/lo                 2 * 1024 * 4 B (x2 buffers)  =  16 KiB
  tile + word-major tile     2 * 128 * 1024 * 4 B          =   1 MiB
The fused kernel's dense stream is a whole-bucket VMEM scratch of ``4 B x
(num_symbols + 2 rows)`` — it is what bounds the bucket a kernel decode can
take; :func:`repro.kernels.ops.check_decode_vmem` refuses larger buckets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "huffman_decode_padded",
    "huffman_decode_tile",
    "huffman_decode_dense",
    "dense_rows",
]

BLOCK_WORDS = 1024  # a multiple of the 1024-word SMEM tile of the sidecar
LANES = 128


def word_block(block_words: int, interpret: bool) -> int:
    """Compiled kernels take the symlen sidecar in SMEM blocks that must
    match its 1024-word tile: round the word block up to one."""
    if interpret:
        return int(block_words)
    return -(-int(block_words) // BLOCK_WORDS) * BLOCK_WORDS


def _shr(x, s):
    return jax.lax.shift_right_logical(x, s)


def table_operands(dec_limit, dec_first, dec_rank, dec_syms):
    """The decode tables as kernel operands: the three small canonical
    tables as int32 (SMEM scalars) and the symbol table as a bf16 row."""
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return (
        i32(dec_limit),
        i32(dec_first),
        i32(dec_rank),
        jnp.asarray(dec_syms, jnp.bfloat16).reshape(1, 256),
    )


def table_specs():
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return [smem, smem, smem, pl.BlockSpec((1, 256), lambda *_: (0, 0))]


def _decode_slot(cur_hi, cur_lo, limit_ref, first_ref, rank_ref, syms_row,
                 *, l_max: int):
    """Decode ONE symbol for every word in the block simultaneously.

    ``cur_hi``/``cur_lo`` are int32[1, BW]; the tables are SMEM refs
    (``limit_ref`` int32[L_max], ``first_ref``/``rank_ref`` int32[L_max+1])
    and ``syms_row`` the bf16[1, 256] symbol table.  Returns (sym int32[1,
    BW], new_hi, new_lo) — the arithmetic canonical decode plus the funnel
    shift that consumes the codeword.  Shared by the staged tile kernel and
    the fused dense kernels.
    """
    prefix = _shr(cur_hi, 32 - l_max)  # [1, BW], < 2^l_max
    # --- code length: compares against the limit boundaries ---
    length = jnp.ones_like(prefix)
    for l in range(l_max):
        length = length + (prefix >= limit_ref[l]).astype(jnp.int32)
    length = jnp.minimum(length, l_max)  # clamp padding-bit garbage
    # --- first_code / rank_offset by selection over lengths ---
    fcs = jnp.zeros_like(prefix)
    roff = jnp.zeros_like(prefix)
    for l in range(1, l_max + 1):
        hit = length == l
        fcs = jnp.where(hit, first_ref[l], fcs)
        roff = jnp.where(hit, rank_ref[l], roff)
    rank = roff + _shr(prefix - fcs, l_max - length)
    rank = jnp.clip(rank, 0, 255)
    # --- symbol: syms[1, 256] @ one-hot[256, BW] on the MXU (exact) ---
    iota = jax.lax.broadcasted_iota(jnp.int32, (256, rank.shape[1]), 0)
    onehot = (iota == rank).astype(jnp.bfloat16)
    sym = jnp.dot(
        syms_row, onehot, preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    # --- funnel-shift the (hi, lo) buffer left by `length` (1..16) ---
    new_hi = (cur_hi << length) | _shr(cur_lo, 32 - length)
    new_lo = cur_lo << length
    return sym, new_hi, new_lo


def _decode_kernel(
    hi_ref,  # int32[1, BW]
    lo_ref,
    limit_ref,
    first_ref,
    rank_ref,
    syms_ref,  # bf16[1, 256]
    out_ref,  # int32[MAX_SYMS, BW]
    *,
    l_max: int,
    max_symlen: int,
):
    syms_row = syms_ref[...]

    def slot(j, carry):
        cur_hi, cur_lo = carry
        sym, new_hi, new_lo = _decode_slot(
            cur_hi, cur_lo, limit_ref, first_ref, rank_ref, syms_row,
            l_max=l_max,
        )
        out_ref[pl.ds(j, 1), :] = sym
        return new_hi, new_lo

    jax.lax.fori_loop(0, max_symlen, slot, (hi_ref[...], lo_ref[...]))


def dense_rows(num_symbols: int, max_symlen: int) -> int:
    """Rows of the lane-dense ``[rows, 128]`` symbol stream a fused decode
    writes: every symbol, one word row of spill, and the row a store may
    touch past it."""
    return -(-(int(num_symbols) + int(max_symlen)) // LANES) + 1


def decode_block_to_dense(
    hi,  # int32[1, BW]
    lo,
    sl_ref,  # SMEM int32[BW] — this block's symlen sidecar
    limit_ref,
    first_ref,
    rank_ref,
    syms_row,
    dense_ref,  # int32[rows, 128] — the lane-dense symbol stream
    tile_ref,  # VMEM scratch int32[128, BW], rows >= max_symlen zero
    tilet_ref,  # VMEM scratch int32[BW, 128]
    base,  # int32 scalar: output offset of this block's first symbol
    *,
    l_max: int,
    max_symlen: int,
):
    """Decode one word block and compact it into ``dense_ref`` at ``base``.

    The in-kernel form of the paper's prefix-scan + cooperative-write
    stage: the slot loop fills the padded tile in VMEM *scratch*, one
    transpose makes it word-major, and a word loop carries the exclusive
    prefix sum of the SMEM symlen sidecar as a scalar offset, storing each
    word's 128-lane row (its ``max_symlen`` slots, zeros after) at
    ``base + offset``.  A row landing at lane ``c`` of stream row ``r`` is
    rotated by ``c`` and written as lanes ``>= c`` of row ``r`` plus lanes
    ``< c`` of row ``r + 1``.  Rows overlap: word ``w``'s garbage tail is
    exactly covered by word ``w+1``'s row, so every position before the
    stream's end holds its true symbol; the one row of spill past the
    block's end is re-zeroed with a zero row.

    Returns the number of symbols this block decoded (int32), so callers
    carrying a running base across sequential grid steps can advance it.
    """

    def slot(j, carry):
        cur_hi, cur_lo = carry
        sym, new_hi, new_lo = _decode_slot(
            cur_hi, cur_lo, limit_ref, first_ref, rank_ref, syms_row,
            l_max=l_max,
        )
        tile_ref[pl.ds(j, 1), :] = sym
        return new_hi, new_lo

    jax.lax.fori_loop(0, max_symlen, slot, (hi, lo))
    tilet_ref[...] = tile_ref[...].T
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def put(p, row):
        r = p // LANES
        c = p % LANES
        rolled = pltpu.roll(row, c, 1)
        head = dense_ref[pl.ds(r, 1), :]
        dense_ref[pl.ds(r, 1), :] = jnp.where(lane >= c, rolled, head)
        tail = dense_ref[pl.ds(r + 1, 1), :]
        dense_ref[pl.ds(r + 1, 1), :] = jnp.where(lane < c, rolled, tail)

    def word(w, off):
        put(base + off, tilet_ref[pl.ds(w, 1), :])
        return off + sl_ref[w]

    decoded = jax.lax.fori_loop(0, hi.shape[1], word, jnp.int32(0))
    # the block's final row wrote < max_symlen junk symbols past its true
    # end; re-zero them.  For interior blocks the next block overwrites the
    # same region with real symbols either way — for the LAST block this is
    # what makes positions beyond the stream read exactly like the XLA
    # scatter's zero fill (so fused and unfused buckets match bit for bit
    # even in padding windows).
    put(base + decoded, jnp.zeros((1, LANES), jnp.int32))
    return decoded


def word_operands(hi, lo, symlen, block_words: int):
    """Pad the bucket words to a block multiple and lay them out as kernel
    operands: (hi, lo) as int32 rows, symlen as int32."""
    w = hi.shape[0]
    num_blocks = max(-(-w // block_words), 1)
    wp = num_blocks * block_words
    i32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    hi = jnp.pad(i32(hi.astype(jnp.uint32)), (0, wp - w)).reshape(1, wp)
    lo = jnp.pad(i32(lo.astype(jnp.uint32)), (0, wp - w)).reshape(1, wp)
    symlen = jnp.pad(symlen.astype(jnp.int32), (0, wp - w))
    return num_blocks, hi, lo, symlen


def _dense_kernel(
    hi_ref,
    lo_ref,
    sl_ref,  # SMEM int32[BW] — the symlen sidecar rides into the kernel
    limit_ref,
    first_ref,
    rank_ref,
    syms_ref,
    out_ref,  # int32[rows, 128] — whole dense stream, revisited every step
    tile_ref,
    tilet_ref,
    base_ref,  # SMEM scratch int32[1]: running output offset across blocks
    *,
    l_max: int,
    max_symlen: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        base_ref[0] = 0
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
        tile_ref[...] = jnp.zeros(tile_ref.shape, tile_ref.dtype)

    base = base_ref[0]
    decoded = decode_block_to_dense(
        hi_ref[...], lo_ref[...], sl_ref, limit_ref, first_ref, rank_ref,
        syms_ref[...], out_ref, tile_ref, tilet_ref, base,
        l_max=l_max, max_symlen=max_symlen,
    )
    base_ref[0] = base + decoded


@functools.partial(
    jax.jit,
    static_argnames=(
        "l_max", "max_symlen", "num_symbols", "block_words", "interpret"
    ),
)
def huffman_decode_dense(
    hi: jnp.ndarray,  # uint32[W]
    lo: jnp.ndarray,  # uint32[W]
    symlen: jnp.ndarray,  # int32[W]
    dec_limit: jnp.ndarray,
    dec_first: jnp.ndarray,
    dec_rank: jnp.ndarray,
    dec_syms: jnp.ndarray,
    *,
    l_max: int,
    max_symlen: int,
    num_symbols: int,
    block_words: int = BLOCK_WORDS,
    interpret: bool,
) -> jnp.ndarray:
    """Fused decode + compaction: packed words -> dense int32[num_symbols].

    ONE ``pallas_call``: the ``[max_symlen, W]`` tile only ever exists as a
    per-block VMEM scratch, and the dense output offsets come from the
    in-kernel running sum of the SMEM symlen sidecar (the cross-block base
    rides SMEM scratch across the sequential grid).  Trailing padding
    words must carry ``symlen == 0``; every position past the true symbol
    total reads as zero (the cooperative store re-zeroes its one row of
    spill), exactly like ``compact_padded_scatter``'s zero fill.
    """
    block_words = max(min(word_block(block_words, interpret), hi.shape[0]), 1)
    num_blocks, hi, lo, symlen = word_operands(hi, lo, symlen, block_words)
    rows = dense_rows(num_symbols, max_symlen)
    kernel = functools.partial(
        _dense_kernel, l_max=l_max, max_symlen=max_symlen
    )
    words = pl.BlockSpec((1, block_words), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[
            words,
            words,
            pl.BlockSpec(
                (block_words,), lambda i: (i,), memory_space=pltpu.SMEM
            ),
            *table_specs(),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((LANES, block_words), jnp.int32),
            pltpu.VMEM((block_words, LANES), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )(hi, lo, symlen,
      *table_operands(dec_limit, dec_first, dec_rank, dec_syms))
    return out.reshape(-1)[:num_symbols]


@functools.partial(
    jax.jit,
    static_argnames=("l_max", "max_symlen", "block_words", "interpret"),
)
def huffman_decode_tile(
    hi: jnp.ndarray,  # uint32[W]
    lo: jnp.ndarray,  # uint32[W]
    dec_limit: jnp.ndarray,
    dec_first: jnp.ndarray,
    dec_rank: jnp.ndarray,
    dec_syms: jnp.ndarray,
    *,
    l_max: int,
    max_symlen: int,
    block_words: int = BLOCK_WORDS,
    interpret: bool,
) -> jnp.ndarray:
    """Decode every word's symbols into a slot-major tile [max_symlen, W]
    (int32) — the kernel's native output layout, no transpose copy.

    The grid iterates over word blocks with no knowledge of container
    boundaries, so a batch of containers concatenated word-wise decodes in
    this single pallas_call; compaction (ops.py / core.symlen) carries the
    per-container structure via the symlen sidecar.

    Words are padded up to a multiple of ``block_words``; callers slice.
    """
    w = hi.shape[0]
    num_blocks, hi, lo, _ = word_operands(
        hi, lo, jnp.zeros((w,), jnp.int32), block_words
    )
    kernel = functools.partial(
        _decode_kernel, l_max=l_max, max_symlen=max_symlen
    )
    words = pl.BlockSpec((1, block_words), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[words, words, *table_specs()],
        out_specs=pl.BlockSpec((max_symlen, block_words), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(
            (max_symlen, num_blocks * block_words), jnp.int32
        ),
        interpret=interpret,
    )(hi, lo, *table_operands(dec_limit, dec_first, dec_rank, dec_syms))
    return out[:, :w]  # [max_symlen, W]


def huffman_decode_padded(*args, **kwargs) -> jnp.ndarray:
    """Word-major view of :func:`huffman_decode_tile`: [W, max_symlen]."""
    return huffman_decode_tile(*args, **kwargs).T
