"""SymLen bitstream format (paper §4.1, Algorithm 1) — pack + parallel unpack.

Codewords are greedily packed MSB-first into fixed 64-bit words; a codeword
never straddles a word boundary.  The *symlen* sidecar stores, per word, the
number of symbols it contains — making every word independently decodable
(the decoder stops after symlen[w] symbols and ignores padding bits).

On-wire format: little-endian uint64 words.  Inside JAX we represent each
word as a (hi, lo) pair of uint32 because TPU int64 is emulated;
``words_to_u32`` / ``u32_to_words`` convert losslessly.

Four implementations:
  * ``pack_symlen_np``      — faithful Algorithm 1, host numpy (the paper's
                              embedded sequential encoder).
  * ``pack_symlen_scan``    — the same algorithm as a ``lax.scan`` (jittable);
                              one scan step per symbol, <=1 word flush per
                              step.  A length-S serial chain: kept as the
                              single-stream reference/baseline.
  * ``pack_symlen_chunked`` — chunk-parallel packing: B scan-lite chunk
                              packs under ``vmap`` (each chunk starts at a
                              fresh word; the scan carries only the O(1)
                              bit-offset/word-index recurrence) stitched by
                              a prefix sum over per-chunk word counts + a
                              gather.  Because every SymLen word is
                              independently decodable, the output decodes
                              bit-exactly with the unchanged decoders, at a
                              cost of < 1 padding word per chunk of stream
                              size.
  * ``unpack_symlen``       — word-parallel decode in pure JAX: lane-per-word
                              slot loop + prefix-sum compaction.  The Pallas
                              kernel in ``repro.kernels.huffman_decode`` is
                              the TPU-tiled version of the same computation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.huffman import HuffmanCodebook

__all__ = [
    "PackedStream",
    "pack_symlen_np",
    "pack_symlen_scan",
    "pack_symlen_chunked",
    "pack_symlen_chunked_parts",
    "stitch_chunk_parts",
    "stitch_capacity",
    "chunk_words_bound",
    "unpack_symlen_np",
    "unpack_symlen",
    "compact_padded_scatter",
    "words_to_u32",
    "u32_to_words",
    "zero_plane_masks",
    "v3_expand_index",
]

WORD_BITS = 64


@dataclasses.dataclass
class PackedStream:
    """A SymLen-packed stream (host container; see core.container for I/O)."""

    words: np.ndarray  # uint64[W]
    symlen: np.ndarray  # int32[W]
    num_symbols: int

    @property
    def num_words(self) -> int:
        return int(self.words.shape[0])

    @property
    def max_symlen(self) -> int:
        return int(self.symlen.max()) if self.symlen.size else 0

    @property
    def payload_bytes(self) -> int:
        # words + symlen sidecar (uint8 is sufficient: symlen <= 64)
        return self.num_words * 8 + self.num_words


def words_to_u32(words: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """uint64[W] -> (hi uint32[W], lo uint32[W])."""
    w = np.asarray(words, dtype=np.uint64)
    hi = (w >> np.uint64(32)).astype(np.uint32)
    lo = (w & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo


def u32_to_words(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (np.asarray(hi, np.uint64) << np.uint64(32)) | np.asarray(
        lo, np.uint64
    )


# ---------------------------------------------------------------------------
# Host reference encoder — Algorithm 1, line for line.
# ---------------------------------------------------------------------------
def pack_symlen_np(symbols: np.ndarray, book: HuffmanCodebook) -> PackedStream:
    symbols = np.asarray(symbols, dtype=np.uint8).ravel()
    codes = book.codes
    lens = book.lengths
    out_words = []
    out_symlen = []
    buffer = 0
    bit_size = 0
    count = 0
    for s in symbols:
        code = int(codes[s])
        code_len = int(lens[s])
        if code_len == 0:
            raise ValueError(f"symbol {s} has no codeword (histogram gap)")
        if bit_size + code_len > WORD_BITS:
            out_words.append(buffer)
            out_symlen.append(count)
            buffer = 0
            bit_size = 0
            count = 0
            # retry same symbol on the fresh word (always fits: len <= 64)
        shift = WORD_BITS - bit_size - code_len
        buffer |= code << shift
        bit_size += code_len
        count += 1
    if count > 0:
        out_words.append(buffer)
        out_symlen.append(count)
    return PackedStream(
        words=np.array(out_words, dtype=np.uint64),
        symlen=np.array(out_symlen, dtype=np.int32),
        num_symbols=int(symbols.size),
    )


# ---------------------------------------------------------------------------
# Device encoders — scan (1 step per symbol) and chunk-parallel.
# ---------------------------------------------------------------------------
def _precheck_symbols(symbols, lengths, num_symbols, valid=None) -> None:
    """Host-side guard against silent corruption: every symbol that occurs in
    the input must have a codeword (``lengths[sym] > 0``).

    A zero-length symbol would emit zero bits yet still increment the word's
    symlen count, so the stream *decodes* — to garbage.  ``pack_symlen_np``
    raises for this; the device packers must reject the same input.  Under
    jit/vmap the operands are tracers and the check is skipped — batched
    callers (``repro.serving.batch_encode``) enforce it with a device-side
    flag checked at drain time instead.
    """
    if any(
        isinstance(x, jax.core.Tracer)
        for x in (symbols, lengths, num_symbols, valid)
    ):
        return
    if valid is not None:
        syms = np.asarray(symbols).ravel()[np.asarray(valid).ravel()]
    else:
        syms = np.asarray(symbols).ravel()[: int(num_symbols)]
    if syms.size == 0:
        return
    lens = np.asarray(lengths).ravel()
    hist = np.bincount(syms.astype(np.int64), minlength=lens.size)
    gaps = np.nonzero((hist[: lens.size] > 0) & (lens == 0))[0]
    if gaps.size:
        raise ValueError(
            f"symbol {int(gaps[0])} has no codeword (histogram gap); "
            f"{gaps.size} distinct input symbol(s) are unencodable"
        )


def pack_symlen_scan(
    symbols: jnp.ndarray,
    codes: jnp.ndarray,  # uint32[256] (right-aligned codewords, len <= 32)
    lengths: jnp.ndarray,  # int32[256]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (hi uint32[W], lo uint32[W], symlen int32[W], num_words int32).

    The faithful Algorithm-1 device transcription — one scan step per
    symbol, carrying the output buffers — kept as the single-stream
    reference and the baseline the chunk-parallel packer is benchmarked
    against.  Output arrays are sized at the worst case (one word per
    symbol); the returned ``num_words`` gives the valid prefix. Codeword
    length is bounded by 32 (L_max <= 16 in practice) so a codeword touches
    at most both halves of the (hi, lo) pair.
    """
    n = symbols.shape[0]
    _precheck_symbols(symbols, lengths, n)
    symbols = symbols.astype(jnp.int32)

    def emit(code: jnp.ndarray, clen: jnp.ndarray, bit_size: jnp.ndarray):
        """Place right-aligned ``code`` of length clen at bit offset bit_size
        (MSB-first) inside a fresh 64-bit (hi, lo) pair."""
        shift = 64 - bit_size - clen  # in [0, 63]
        c = code.astype(jnp.uint32)
        # hi receives bits of code shifted by (shift - 32) when shift >= 32
        hi = jnp.where(
            shift >= 32,
            _shl32(c, shift - 32),
            _shr32(c, 32 - shift),
        )
        lo = jnp.where(shift >= 32, jnp.uint32(0), _shl32(c, shift))
        return hi, lo

    def step(carry, sym):
        w, count, bhi, blo, bit_size, out_hi, out_lo, out_sl = carry
        code = codes[sym]
        clen = lengths[sym]
        flush = bit_size + clen > WORD_BITS
        # flush current word
        out_hi = jnp.where(flush, out_hi.at[w].set(bhi), out_hi)
        out_lo = jnp.where(flush, out_lo.at[w].set(blo), out_lo)
        out_sl = jnp.where(flush, out_sl.at[w].set(count), out_sl)
        w = jnp.where(flush, w + 1, w)
        bhi = jnp.where(flush, jnp.uint32(0), bhi)
        blo = jnp.where(flush, jnp.uint32(0), blo)
        bit_size = jnp.where(flush, 0, bit_size)
        count = jnp.where(flush, 0, count)
        # append symbol
        add_hi, add_lo = emit(code, clen, bit_size)
        bhi = bhi | add_hi
        blo = blo | add_lo
        bit_size = bit_size + clen
        count = count + 1
        return (w, count, bhi, blo, bit_size, out_hi, out_lo, out_sl), None

    init = (
        jnp.int32(0),
        jnp.int32(0),
        jnp.uint32(0),
        jnp.uint32(0),
        jnp.int32(0),
        jnp.zeros((n,), jnp.uint32),
        jnp.zeros((n,), jnp.uint32),
        jnp.zeros((n,), jnp.int32),
    )
    (w, count, bhi, blo, _, out_hi, out_lo, out_sl), _ = jax.lax.scan(
        step, init, symbols
    )
    # final partial word
    has_tail = count > 0
    out_hi = jnp.where(has_tail, out_hi.at[w].set(bhi), out_hi)
    out_lo = jnp.where(has_tail, out_lo.at[w].set(blo), out_lo)
    out_sl = jnp.where(has_tail, out_sl.at[w].set(count), out_sl)
    num_words = w + has_tail.astype(jnp.int32)
    return out_hi, out_lo, out_sl, num_words


def _pack_chunk(
    symbols: jnp.ndarray,  # int32[M]
    valid: jnp.ndarray,  # bool[M] — padding slots pack to nothing
    codes: jnp.ndarray,  # uint32[256]
    lengths: jnp.ndarray,  # int32[256]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Greedy packing of one chunk, scan-lite and scatter-free (vmappable).

    Returns (hi uint32[M], lo uint32[M], symlen int32[M], num_words int32);
    the valid word prefix is ``num_words``.

    The (code, length) table lookup happens here; the packing math itself
    lives in :func:`_pack_chunk_emit` so the fused Pallas encode kernel
    (``repro.kernels.encode_fused``), which looks the tables up via the
    one-hot MXU idiom instead of a gather, runs the *same* emit code —
    that sharing is what makes the kernel path bit-identical by
    construction.
    """
    m = symbols.shape[0]
    if m == 0:
        z = jnp.zeros((0,), jnp.uint32)
        return z, z, jnp.zeros((0,), jnp.int32), jnp.int32(0)
    # masked slots emit a zero-length, zero-valued code: a no-op
    code = jnp.where(valid, codes[symbols], jnp.uint32(0))
    clen = jnp.where(valid, lengths[symbols], 0)
    return _pack_chunk_emit(code, clen, valid)


def _pack_chunk_emit(
    code: jnp.ndarray,  # uint32[M] right-aligned codewords (0 when masked)
    clen: jnp.ndarray,  # int32[M] codeword lengths (0 when masked)
    valid: jnp.ndarray,  # bool[M]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Greedy word materialization from per-symbol (code, length) pairs.

    The only truly sequential part of greedy packing is the (bit offset,
    word index) recurrence — an O(1) carry per symbol — so that is *all* the
    ``lax.scan`` computes (carrying the output buffers instead, as
    ``pack_symlen_scan`` does, costs an O(M) select per step and is
    quadratic).  Word materialization happens outside the scan with no
    scatter (CPU XLA scatters serialize): symbol bits within a word occupy
    disjoint slots, so each word is a *segment sum* of per-symbol shifted
    codes — and since ``word_idx`` is sorted, segment sums are differences
    of one cumulative sum at segment boundaries found by ``searchsorted``
    (uint32 overflow wraps; differences stay exact mod 2^32).
    """
    m = code.shape[0]

    def step(carry, cl):
        bit_size, w = carry
        flush = bit_size + cl > WORD_BITS
        w = w + flush.astype(jnp.int32)
        start = jnp.where(flush, 0, bit_size)
        return (start + cl, w), (w, start)

    _, (word_idx, start) = jax.lax.scan(
        step, (jnp.int32(0), jnp.int32(0)), clen
    )
    # place right-aligned `code` of length clen at bit offset `start`
    # (MSB-first) of its word: hi takes the bits when shift >= 32
    shift = WORD_BITS - start - clen  # in [0, 64]; 64 only for clen == 0
    add_hi = jnp.where(
        shift >= 32, _shl32(code, shift - 32), _shr32(code, 32 - shift)
    )
    add_lo = jnp.where(shift >= 32, jnp.uint32(0), _shl32(code, shift))
    zero_u = jnp.zeros((1,), jnp.uint32)
    zero_i = jnp.zeros((1,), jnp.int32)
    csum_hi = jnp.concatenate([zero_u, jnp.cumsum(add_hi)])
    csum_lo = jnp.concatenate([zero_u, jnp.cumsum(add_lo)])
    csum_sl = jnp.concatenate([zero_i, jnp.cumsum(valid.astype(jnp.int32))])
    # word w covers symbols [right[w-1], right[w]): word indices are
    # contiguous from 0, so one searchsorted gives both boundaries
    w_range = jnp.arange(m, dtype=jnp.int32)
    right = jnp.searchsorted(
        word_idx, w_range, side="right", method="scan_unrolled"
    ).astype(jnp.int32)
    left = jnp.concatenate([zero_i, right[:-1]])
    out_hi = csum_hi[right] - csum_hi[left]
    out_lo = csum_lo[right] - csum_lo[left]
    out_sl = csum_sl[right] - csum_sl[left]
    num_words = jnp.max(jnp.where(valid, word_idx + 1, 0))
    return out_hi, out_lo, out_sl, num_words


def pack_symlen_chunked(
    symbols: jnp.ndarray,
    codes: jnp.ndarray,  # uint32[256]
    lengths: jnp.ndarray,  # int32[256]
    *,
    chunk_size: int,
    num_symbols=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunk-parallel SymLen packing (Algorithm 1, chunk-lifted).

    Splits the stream into ``B = ceil(S / chunk_size)`` fixed-size chunks,
    packs each greedily starting at a fresh 64-bit word — a ``vmap`` of B
    scan-lite chunk packs instead of one serial scan of length S — then
    stitches the per-chunk word runs into one dense stream via a prefix sum
    over per-chunk word counts + a gather.

    **Decoder compatibility.**  SymLen words are independently decodable (the
    sidecar says how many symbols each word holds; trailing pad bits are
    ignored), so *any* symbol→word assignment that preserves symbol order and
    respects the 64-bit capacity is a legal stream.  Starting a fresh word at
    each chunk boundary is therefore invisible to the unchanged serial /
    word-parallel / Pallas decoders: the output decodes bit-exactly.  Cost:
    each chunk boundary wastes at most the tail of one word, i.e. the stream
    grows by < 1 word per chunk vs the sequential packer (with
    ``chunk_size = S`` the output is bit-identical to ``pack_symlen_np``).

    Args:
      symbols: integer[S] symbol stream.
      codes / lengths: encode tables.
      chunk_size: symbols per chunk (static under jit).
      num_symbols: optional true symbol count (host int or device scalar) —
        symbols at index >= num_symbols are padding and pack to nothing.
        Defaults to S.  This is what lets the batched encoder stack
        shape-bucketed signals without corrupting their streams.

    Returns:
      (hi uint32[C], lo uint32[C], symlen int32[C], num_words int32) with
      capacity ``C = B * chunk_size``; the valid prefix is ``num_words``.
    """
    chunk_hi, chunk_lo, chunk_sl, wpc = pack_symlen_chunked_parts(
        symbols, codes, lengths, chunk_size=chunk_size,
        num_symbols=num_symbols,
    )
    num_chunks, _ = chunk_hi.shape
    return stitch_chunk_parts(
        chunk_hi, chunk_lo, chunk_sl, wpc,
        capacity=num_chunks * chunk_size,
    )


def chunk_words_bound(chunk_size: int, l_max: int) -> int:
    """Static upper bound on the words one chunk of ``chunk_size`` symbols
    can pack to — host-computable, so device-resident consumers of chunk
    parts (the transcode pipeline) can size stitched streams without a host
    sync on the true word counts.

    A word is flushed only when the next codeword (<= ``l_max`` bits) does
    not fit, so every flushed word carries more than ``64 - l_max`` bits and
    therefore at least ``floor(64 / l_max)`` symbols; only the chunk's last
    word may hold fewer (>= 1).  Hence
    ``words <= (chunk_size - 1) // floor(64 / l_max) + 1`` (and trivially
    ``words <= chunk_size``).
    """
    if chunk_size <= 0:
        return 0
    s_min = max(WORD_BITS // max(int(l_max), 1), 1)
    return min(int(chunk_size), (int(chunk_size) - 1) // s_min + 1)


# Stitched-stream capacities quantize to this grid so jit specializations of
# downstream decode stay O(log sizes) even when capacities are exact counts.
STITCH_CAPACITY_GRID = 256


def stitch_capacity(words: int, *, grid: int = STITCH_CAPACITY_GRID) -> int:
    """Round a stitched-stream word capacity up to the compile grid.

    ``words`` may be the static worst-case bound (``chunk_words_bound``
    summed over chunks) or — when the caller tolerates one pre-decode sync
    on ``words_per_chunk`` — the exact packed word count; the grid bounds
    the number of distinct static capacities (hence XLA specializations of
    the bucket decode) either way.  Deliberately NOT a power of two: the
    bound is already ~2-3x the true word count and decode slot work is
    linear in capacity, so p2 rounding on top would double it again.
    """
    return -(-max(int(words), 1) // grid) * grid


@functools.partial(jax.jit, static_argnames=("capacity",))
def stitch_chunk_parts(
    chunk_hi: jnp.ndarray,  # uint32[B, C]
    chunk_lo: jnp.ndarray,  # uint32[B, C]
    chunk_sl: jnp.ndarray,  # int32[B, C]
    words_per_chunk: jnp.ndarray,  # int32[B]
    *,
    capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-side stitch: chunk parts -> one dense decoder-shaped stream.

    Chunk b's valid words (its row's first ``words_per_chunk[b]`` entries)
    land in the output run ``[cum[b-1], cum[b])`` — a pure gather (output
    position -> source chunk/slot), scatter-free, all on device.  Positions
    past the total word count are zero words with ``symlen == 0``, which
    every decoder treats as contributing no symbols — so the output is
    directly consumable as a (padded) concatenated bucket stream by
    ``unpack_symlen`` / the Pallas kernel / ``BatchDecoder.decode_streams``.

    ``capacity`` must be a static host-side bound on the total word count
    (exact counts are device-resident); :func:`chunk_words_bound` gives a
    safe per-chunk bound and :func:`stitch_capacity` the compile-grid
    rounding the serving executor's staging contract expects (its inputs
    may live on any shard's device — the stitch follows them, so per-shard
    streams never leave their device).  Multi-signal chunk parts
    ``[K, B, C]`` stitch to
    one concatenated multi-signal stream by reshaping to ``[K * B, C]`` —
    row order is signal order, so the segment structure the symlen sidecar
    induces matches the per-signal window metadata.

    Returns (hi uint32[capacity], lo uint32[capacity], symlen
    int32[capacity], num_words int32) — ``num_words`` (a device scalar; no
    sync) is the live prefix.
    """
    b = chunk_hi.shape[0]
    if b == 0 or capacity == 0:
        z = jnp.zeros((capacity,), jnp.uint32)
        return z, z, jnp.zeros((capacity,), jnp.int32), jnp.int32(0)
    wpc = words_per_chunk.astype(jnp.int32)
    cum = jnp.cumsum(wpc)  # inclusive prefix sum, int32[B]
    pos = jnp.arange(capacity, dtype=jnp.int32)
    src = jnp.minimum(
        jnp.searchsorted(cum, pos, side="right"), b - 1
    ).astype(jnp.int32)
    slot = jnp.minimum(pos - (cum[src] - wpc[src]), chunk_hi.shape[1] - 1)
    live = pos < cum[-1]
    return (
        jnp.where(live, chunk_hi[src, slot], jnp.uint32(0)),
        jnp.where(live, chunk_lo[src, slot], jnp.uint32(0)),
        jnp.where(live, chunk_sl[src, slot], 0),
        cum[-1],
    )


def pack_symlen_chunked_parts(
    symbols: jnp.ndarray,
    codes: jnp.ndarray,  # uint32[256]
    lengths: jnp.ndarray,  # int32[256]
    *,
    chunk_size: int,
    num_symbols=None,
    valid=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The un-stitched form of :func:`pack_symlen_chunked`.

    Returns (hi uint32[B, chunk_size], lo uint32[B, chunk_size],
    symlen int32[B, chunk_size], words_per_chunk int32[B]): chunk b's valid
    words are its row's first ``words_per_chunk[b]`` entries, and the dense
    stream is their in-order concatenation.  The batched encode engine
    consumes this directly — draining chunk runs and concatenating on the
    host is cheaper than a device-side gather stitch, and the stream bytes
    are identical either way.

    ``valid`` (bool[S], mutually exclusive with ``num_symbols``) masks an
    arbitrary — not necessarily prefix — subset of slots: masked slots emit
    nothing, advance nothing, and are not counted in the symlen sidecar, so
    the packed stream equals the greedy pack of the *compacted* valid
    subsequence.  This is what makes container-v3 zero-plane suppression
    free at encode time: the suppressed grid cells are simply masked out.
    """
    if chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    s = symbols.shape[0]
    num_chunks = max(-(-s // chunk_size), 1)
    cap = num_chunks * chunk_size
    if valid is not None:
        if num_symbols is not None:
            raise ValueError("pass num_symbols or valid, not both")
        _precheck_symbols(symbols, lengths, None, valid)
        valid = valid.astype(bool)
        if cap != s:
            valid = jnp.pad(valid, (0, cap - s))
    else:
        if num_symbols is None:
            num_symbols = s
        _precheck_symbols(symbols, lengths, num_symbols)
        nsym = jnp.asarray(num_symbols, jnp.int32)
        valid = jnp.arange(cap, dtype=jnp.int32) < nsym
    symbols = symbols.astype(jnp.int32)
    if cap != s:
        symbols = jnp.pad(symbols, (0, cap - s))
    return jax.vmap(_pack_chunk, in_axes=(0, 0, None, None))(
        symbols.reshape(num_chunks, chunk_size),
        valid.reshape(num_chunks, chunk_size),
        codes,
        lengths,
    )


# ---------------------------------------------------------------------------
# Container-v3 zero-plane stream layout (host-side reference).
#
# With zero-plane suppression, the coded symbol stream omits every grid cell
# (w, k) lying in an all-zero-bin window row (zrow[w]) or coefficient column
# (zcol[k]) of the coded level grid.  The two helpers below define the ONE
# canonical mapping between the dense coded stream and the flat [W, E] grid
# — the encoder's suppression mask and the decoder's expansion index are
# both derived from it, so encode and decode can never disagree about
# stream order (row-major over the surviving cells).
# ---------------------------------------------------------------------------
def zero_plane_masks(grid: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(zrow bool[W], zcol bool[E]) of a coded level grid ``[W, E]``.

    ``zrow[w]``: every band of window w coded to the zero bin 128.
    ``zcol[k]``: band k coded to 128 in every window (all-zero rows are
    themselves all-128, so including them cannot flip a column).
    A cell is suppressed iff its row OR column is a zero plane; the
    surviving cell count is rectangular: (W - nzrow) * (E - nzcol).
    """
    grid = np.asarray(grid)
    zrow = np.all(grid == 128, axis=1)
    zcol = np.all(grid == 128, axis=0)
    return zrow, zcol


def v3_expand_index(
    members,
    e: int,
    *,
    total_windows: int = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Expansion metadata for a (possibly concatenated) v3 coded stream.

    ``members`` is a sequence of ``(num_windows, zrow, zcol)`` per signal in
    stream order (``zrow``/``zcol`` may be None for no suppression);
    ``total_windows`` pads the grid to the decode bucket's rounded window
    count.  Returns:

      idx int32[total_windows * e] — for each flat grid cell, its position
        in the dense coded stream (concatenation of the members' coded
        symbols), or -1 where the cell is suppressed or bucket padding
        (those expand to the zero bin — see ``quantize.expand_coded_stream``).
      seg_start int32[total_windows] — the index of the first window of the
        cell's signal (its own index for padding windows, making each one a
        degenerate single-window segment that unpredicts to all-128), the
        segment structure ``quantize.unpredict_levels`` needs so prediction
        never crosses a signal boundary.
    """
    win_off = 0
    sym_off = 0
    nw_total = sum(int(m[0]) for m in members)
    if total_windows is None:
        total_windows = nw_total
    if total_windows < nw_total:
        raise ValueError(
            f"total_windows={total_windows} < member windows {nw_total}"
        )
    idx = np.full(total_windows * e, -1, dtype=np.int32)
    seg_start = np.arange(total_windows, dtype=np.int32)
    for num_windows, zrow, zcol in members:
        w = int(num_windows)
        mask = np.ones((w, e), dtype=bool)
        if zrow is not None:
            mask &= ~np.asarray(zrow, dtype=bool)[:, None]
        if zcol is not None:
            mask &= ~np.asarray(zcol, dtype=bool)[None, :]
        flat = mask.ravel()
        ncoded = int(np.count_nonzero(flat))
        local = np.cumsum(flat) - 1  # rank of each coded cell, row-major
        span = idx[win_off * e: win_off * e + w * e]
        span[flat] = (local[flat] + sym_off).astype(np.int32)
        seg_start[win_off: win_off + w] = win_off
        win_off += w
        sym_off += ncoded
    return idx, seg_start


def _shl32(x: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """uint32 left shift, defined 0 for s >= 32 or s < 0."""
    s32 = jnp.clip(s, 0, 31).astype(jnp.uint32)
    val = x << s32
    return jnp.where((s >= 32) | (s < 0), jnp.uint32(0), val)


def _shr32(x: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """uint32 logical right shift, defined 0 for s >= 32 or s < 0."""
    s32 = jnp.clip(s, 0, 31).astype(jnp.uint32)
    val = x >> s32
    return jnp.where((s >= 32) | (s < 0), jnp.uint32(0), val)


# ---------------------------------------------------------------------------
# Host reference decoder (bit-serial, LUT-based — the paper's GPU semantics).
# ---------------------------------------------------------------------------
def unpack_symlen_np(
    stream: PackedStream, book: HuffmanCodebook
) -> np.ndarray:
    out = np.empty(stream.num_symbols, dtype=np.uint8)
    pos = 0
    lmax = book.l_max
    mask = (1 << lmax) - 1
    for w, sl in zip(stream.words, stream.symlen):
        cur = int(w)
        consumed = 0
        for _ in range(int(sl)):
            window = (cur >> max(WORD_BITS - lmax, 0)) & mask
            # if fewer than lmax bits remain, low bits are zero padding —
            # prefix-free codes still decode correctly (paper §4.2.1)
            sym = book.lut_symbol[window]
            l = int(book.lut_length[window])
            cur = (cur << l) & ((1 << WORD_BITS) - 1)
            consumed += l
            out[pos] = sym
            pos += 1
        assert consumed <= WORD_BITS
    assert pos == stream.num_symbols
    return out


def compact_padded_scatter(
    padded: jnp.ndarray,  # [W, max_symlen] (any integer dtype)
    symlen: jnp.ndarray,  # int32[W]
    num_symbols: int,
) -> jnp.ndarray:
    """Compact a padded per-word symbol tile to a dense ``[num_symbols]``.

    Segment-aware scatter: one exclusive prefix-sum over the symlen sidecar
    gives every word its output offset, then all (word, slot) pairs scatter
    simultaneously — slot ``j`` of word ``w`` lands at ``offsets[w] + j`` when
    ``j < symlen[w]`` and is dropped otherwise.  This replaces the per-symbol
    ``searchsorted`` gather (O(T log W) index searches) with a single
    O(W * max_symlen) scatter, and — because the offsets are *segment* sums —
    it is oblivious to container boundaries: concatenated multi-container
    streams compact in the same dispatch (the paper's prefix-scan +
    cooperative-write stage, batch-lifted).

    Padding words (symlen == 0) and tail slots contribute nothing; output
    positions beyond the last real symbol stay zero.
    """
    w, max_symlen = padded.shape
    with jax.named_scope("fptc.decode.compact"):
        symlen = symlen.astype(jnp.int32)
        offsets = jnp.cumsum(symlen) - symlen  # exclusive prefix sum
        slot = jnp.arange(max_symlen, dtype=jnp.int32)
        idx = offsets[:, None] + slot[None, :]  # [W, max_symlen]
        valid = slot[None, :] < symlen[:, None]
        # invalid lanes scatter out of bounds and are dropped
        idx = jnp.where(valid, idx, num_symbols)
        out = jnp.zeros((num_symbols,), dtype=padded.dtype)
        return out.at[idx.ravel()].set(padded.ravel(), mode="drop")


# ---------------------------------------------------------------------------
# Word-parallel decoder — pure JAX (XLA); mirrors the Pallas kernel exactly.
# ---------------------------------------------------------------------------
def unpack_symlen(
    hi: jnp.ndarray,  # uint32[W]
    lo: jnp.ndarray,  # uint32[W]
    symlen: jnp.ndarray,  # int32[W]
    dec_limit: jnp.ndarray,  # uint32[L_max] = limit_shifted[1:]
    dec_first: jnp.ndarray,  # uint32[L_max + 1] = first_code_shifted
    dec_rank: jnp.ndarray,  # int32[L_max + 1]  = rank_offset
    dec_syms: jnp.ndarray,  # int32[256]        = sorted_symbols
    l_max: int,
    max_symlen: int,
    num_symbols: int,
) -> jnp.ndarray:
    """Decode all words in parallel and compact to a dense uint8[num_symbols].

    Per slot iteration (over ``max_symlen`` slots), ALL words decode one
    symbol simultaneously:
      1. prefix  = top L_max bits of the remaining buffer (lives in hi)
      2. length  = 1 + sum_l [prefix >= limit_shifted[l]]   (vector compares)
      3. rank    = rank_offset[len] + ((prefix - first_code_shifted[len])
                   >> (L_max - len))
      4. symbol  = sorted_symbols[rank]
      5. funnel-shift (hi, lo) left by length
    Compaction: :func:`compact_padded_scatter` — a segment-aware scatter
    driven by one exclusive prefix-sum of symlen (the XLA lift of the paper's
    prefix-scan + warp-cooperative write stage); works unchanged on
    concatenated multi-container streams.
    """

    def slot_step(carry, _):
        cur_hi, cur_lo = carry
        prefix = _shr32(cur_hi, 32 - l_max)  # uint32[W]
        ge = prefix[None, :] >= dec_limit[:, None]  # [L_max, W]
        length = 1 + jnp.sum(ge.astype(jnp.int32), axis=0)
        length = jnp.minimum(length, l_max)  # clamp garbage/padding prefixes
        fcs = dec_first[length]
        rank = dec_rank[length] + (
            _shr32(prefix - fcs, l_max - length)
        ).astype(jnp.int32)
        rank = jnp.clip(rank, 0, 255)
        sym = dec_syms[rank].astype(jnp.uint8)
        # funnel shift left by `length` (1 <= length <= l_max <= 16 < 32)
        new_hi = _shl32(cur_hi, length) | _shr32(cur_lo, 32 - length)
        new_lo = _shl32(cur_lo, length)
        return (new_hi, new_lo), sym

    with jax.named_scope("fptc.decode.huffman"):
        (_, _), padded = jax.lax.scan(
            slot_step, (hi, lo), None, length=max_symlen
        )  # padded: uint8[max_symlen, W]
    return compact_padded_scatter(padded.T, symlen, num_symbols)
