"""Pallas TPU kernel: the encode bucket's entropy stage — codeword lookup +
greedy SymLen pack + word materialization in one ``pallas_call``.

The serving engine's encode is DCT + quantize (+ the container-v3 coding
stage) down to a symbol grid, then the chunk-parallel SymLen pack
(``repro.core.symlen.pack_symlen_chunked_parts``).  The front half is
elementwise work and two small matmuls that XLA already fuses well; the
pack is where XLA pays: one ``while`` iteration per symbol slot, plus a
cumsum, a searchsorted and three gathers per chunk.  This kernel runs the
whole pack in VMEM:

  layout   the bucket's chunks side by side in lanes: ``syms[C, L]`` holds
           slot ``j`` of chunk ``l`` at ``[j, l]`` (every signal row's
           chunks, the rows concatenated; masked slots carry -1), so a
           grid step packs ``block_lanes`` independent chunks at once;
  lookup   (code, length) per slot by integer selection against the
           256-entry tables in SMEM — no gather, no float cast;
  pack     one sequential loop over the C slots carrying only the O(1)
           (bit offset, word index) recurrence per lane — the recurrence
           of ``symlen._pack_chunk_emit``;
  words    each word is the sum of its symbols' shifted codes (the bits
           are disjoint, so the int32 sum is the OR), one masked
           sublane-reduction per word slot up to the block's widest chunk.

The output is exactly the XLA packer's chunk parts — first
``words_per_chunk`` words per chunk, zeros after — so the two arms are
byte-identical, and the stream is the same bits on every backend.

VMEM per grid step (C slots, LB lanes, Wb = word slots per chunk):
  syms in (double-buffered)          2 * 4 B * C * LB
  code / length / index / offset     4 * 4 B * C * LB  (scratch)
  the body's [C, LB] values          ~6 * 4 B * C * LB (masks, shifts, ...)
  hi / lo / symlen out (dbl-buf)     6 * 4 B * Wb * LB
At C = 1024, LB = 128 that is ~7 MiB.  Compiled for a TPU v5e (128 MiB of
VMEM, a 96 MiB scoped limit), C = 12288 fits and C = 16384 does not;
``repro.kernels.ops`` refuses a bucket whose step would not fit
(:class:`~repro.kernels.ops.KernelLimitError`) — exact-mode encodes (one
chunk per signal) reach that at about 12k symbols per signal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["encode_pack", "BLOCK_LANES", "step_vmem_bytes"]

BLOCK_LANES = 128
WORD_BITS = 64


def _shl(x, s):
    """int32 left shift, 0 for s >= 32 or s < 0 (``symlen._shl32``)."""
    v = x << jnp.clip(s, 0, 31)
    return jnp.where((s >= 32) | (s < 0), 0, v)


def _shr(x, s):
    """int32 logical right shift, 0 for s >= 32 or s < 0."""
    v = jax.lax.shift_right_logical(x, jnp.clip(s, 0, 31))
    return jnp.where((s >= 32) | (s < 0), 0, v)


def _kernel(
    codes_ref,  # SMEM int32[256] right-aligned codewords
    lengths_ref,  # SMEM int32[256]
    syms_ref,  # int32[C, LB] symbol per slot, -1 = masked slot
    hi_ref,  # out int32[Wb, LB]
    lo_ref,  # out int32[Wb, LB]
    sl_ref,  # out int32[Wb, LB]
    wpc_ref,  # out int32[1, LB]
    code_s,  # VMEM scratch int32[C, LB]: code, then hi contribution
    len_s,  # VMEM scratch int32[C, LB]: length, then lo contribution
    widx_s,  # VMEM scratch int32[C, LB]: word index per slot
    start_s,  # VMEM scratch int32[C, LB]: bit offset per slot
):
    syms = syms_ref[...]

    def lookup(v, carry):
        code, clen = carry
        hit = syms == v
        return (
            jnp.where(hit, codes_ref[v], code),
            jnp.where(hit, lengths_ref[v], clen),
        )

    zero = jnp.zeros(syms.shape, jnp.int32)
    code, clen = jax.lax.fori_loop(0, 256, lookup, (zero, zero))
    code_s[...] = code
    len_s[...] = clen

    def slot(j, carry):
        bit, w = carry
        cl = len_s[pl.ds(j, 1), :]
        flush = bit + cl > WORD_BITS
        w = w + flush.astype(jnp.int32)
        start = jnp.where(flush, 0, bit)
        widx_s[pl.ds(j, 1), :] = w
        start_s[pl.ds(j, 1), :] = start
        return start + cl, w

    row0 = jnp.zeros((1, syms.shape[1]), jnp.int32)
    jax.lax.fori_loop(0, syms.shape[0], slot, (row0, row0))

    valid = syms >= 0
    widx = widx_s[...]
    # right-aligned `code` of length clen lands at bit offset `start`
    # (MSB-first): hi takes the bits when shift >= 32
    shift = WORD_BITS - start_s[...] - clen
    code_s[...] = jnp.where(
        shift >= 32, _shl(code, shift - 32), _shr(code, 32 - shift)
    )
    len_s[...] = jnp.where(shift >= 32, 0, _shl(code, shift))
    wpc = jnp.max(jnp.where(valid, widx + 1, 0), axis=0, keepdims=True)
    wpc_ref[...] = wpc

    hi_ref[...] = jnp.zeros(hi_ref.shape, jnp.int32)
    lo_ref[...] = jnp.zeros(lo_ref.shape, jnp.int32)
    sl_ref[...] = jnp.zeros(sl_ref.shape, jnp.int32)

    def word(k, _):
        hit = widx_s[...] == k
        hi_ref[pl.ds(k, 1), :] = jnp.sum(
            jnp.where(hit, code_s[...], 0), axis=0, keepdims=True
        )
        lo_ref[pl.ds(k, 1), :] = jnp.sum(
            jnp.where(hit, len_s[...], 0), axis=0, keepdims=True
        )
        sl_ref[pl.ds(k, 1), :] = jnp.sum(
            (hit & (syms_ref[...] >= 0)).astype(jnp.int32), axis=0,
            keepdims=True,
        )
        return 0

    # a chunk with no valid slot still owns word 0 (zero bits, symlen 0),
    # exactly like the XLA packer's segment sums
    jax.lax.fori_loop(0, jnp.max(wpc), word, 0)


def step_vmem_bytes(chunk_size: int, word_slots: int, lanes: int) -> int:
    """VMEM one grid step holds (see the module docstring)."""
    return 4 * lanes * (12 * chunk_size + 6 * word_slots + 2)


@functools.partial(
    jax.jit,
    static_argnames=(
        "word_slots", "block_lanes", "vmem_limit_bytes", "interpret"
    ),
)
def encode_pack(
    syms: jnp.ndarray,  # int32[C, L] slot-major chunks, -1 = masked slot
    codes: jnp.ndarray,  # uint32[256]
    lengths: jnp.ndarray,  # int32[256]
    *,
    word_slots: int,
    block_lanes: int,
    vmem_limit_bytes: int = None,
    interpret: bool,
):
    """Pack ``L`` chunks of ``C`` slots: returns ``(hi uint32[Wb, L], lo
    uint32[Wb, L], symlen int32[Wb, L], words_per_chunk int32[L])`` with
    ``Wb = word_slots`` (a static bound on any chunk's word count, e.g.
    :func:`repro.core.symlen.chunk_words_bound`).  Lanes are padded to a
    ``block_lanes`` multiple with all-masked chunks and sliced back."""
    c, l = syms.shape
    lb = l if l <= block_lanes else block_lanes
    lp = -(-l // lb) * lb
    if lp != l:
        syms = jnp.pad(syms, ((0, 0), (0, lp - l)), constant_values=-1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    lanes = pl.BlockSpec((c, lb), lambda i: (0, i))
    words = pl.BlockSpec((word_slots, lb), lambda i: (0, i))
    hi, lo, sl, wpc = pl.pallas_call(
        _kernel,
        grid=(lp // lb,),
        in_specs=[smem, smem, lanes],
        out_specs=[words, words, words, pl.BlockSpec((1, lb), lambda i: (0, i))],
        out_shape=[
            jax.ShapeDtypeStruct((word_slots, lp), jnp.int32),
            jax.ShapeDtypeStruct((word_slots, lp), jnp.int32),
            jax.ShapeDtypeStruct((word_slots, lp), jnp.int32),
            jax.ShapeDtypeStruct((1, lp), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((c, lb), jnp.int32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(
        jax.lax.bitcast_convert_type(codes.astype(jnp.uint32), jnp.int32),
        lengths.astype(jnp.int32),
        syms,
    )
    u32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.uint32)
    return u32(hi[:, :l]), u32(lo[:, :l]), sl[:, :l], wpc[0, :l]
