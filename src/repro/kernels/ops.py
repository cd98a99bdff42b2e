"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the codec (``repro.core.codec`` with
``use_kernels=True``) and the serving/benchmark layers call.  This module
alone decides whether a kernel runs compiled by Mosaic (on a TPU backend)
or in Pallas interpret mode (everywhere else, which is how the CPU test
suite runs them); the raw kernels take ``interpret`` as a required
argument.

The kernel surface:

  * :func:`huffman_decode` — ONE dispatch: the fused dense kernel decodes
    and compacts in the same ``pallas_call`` (the symlen sidecar rides into
    the kernel; no ``[max_symlen, W]`` HBM tile).
  * :func:`decode_bucket_fused` — the full decode megakernel: Huffman +
    compaction + LUT dequant + iDCT in a single ``pallas_call``.
  * :func:`encode_bucket_fused` — the encode entropy stage: codeword lookup
    + greedy SymLen pack + word materialization in one ``pallas_call``,
    byte-identical to the XLA chunk packer.
  * :func:`idct_dequant` / :func:`dct_quant` — the staged per-stage tiles
    (the fixed-rate kernel arm, the legacy per-container baseline, and
    oracles).

Every wrapper guards what the kernels cannot take before dispatch, with a
typed error and never a silent switch to another path:

  * int32 offsets (:func:`check_i32_offsets`): symbol/word offsets inside
    the kernels are int32, so a bucket whose dense symbol stream would
    cross the 2^31 mark must raise instead of wrapping offsets negative;
  * VMEM (:class:`KernelLimitError`): the decode megakernel keeps the
    bucket's whole dense symbol stream in VMEM scratch and the pack kernel
    a ``[chunk, lanes]`` working set, so buckets past the recorded limits
    are refused (decode in smaller batches, or page the scratch — future
    work);
  * the container-v3 decode epilogue (gathers + a prefix sum) has no Mosaic
    lowering, so v3 buckets are refused on the chip.

:func:`decode_kernel_fits` asks the same questions of a decode bucket
without raising: the decode engine's per-bucket arm choice uses it to keep
such buckets on the XLA arm.

The megakernel wrappers resolve their Pallas block sizes at TRACE time:
``block_*=None`` (the engines' calling convention) consults the
:mod:`repro.tuning.autotune` cache for this (backend, plan key, bucket
shape) and falls back to the built-in defaults when nothing is tuned.
Blocks change tiling only — never bytes — and the engines key their jits
on the tuning-cache epoch so a new entry forces a retrace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dct as _dct
from repro.core.calibration import DeviceTables
from repro.core.quantize import QuantTable
from repro.core.symlen import chunk_words_bound
from repro.kernels import dct_quant as _dq
from repro.kernels import decode_fused as _df
from repro.kernels import encode_fused as _ef
from repro.kernels import huffman_decode as _hd
from repro.kernels import idct_dequant as _idq
from repro.tuning.autotune import tuned_blocks as _tuned_blocks

__all__ = [
    "huffman_decode",
    "decode_bucket_fused",
    "encode_bucket_fused",
    "idct_dequant",
    "dct_quant",
    "check_i32_offsets",
    "check_decode_vmem",
    "check_encode_vmem",
    "decode_vmem_bytes",
    "decode_kernel_fits",
    "KernelLimitError",
    "VMEM_BUDGET_BYTES",
    "VMEM_LIMIT_BYTES",
    "on_tpu",
]

_I32_MAX = np.iinfo(np.int32).max
_TRIVIAL_CODING = (0, 0, False)

# The VMEM the kernels ask Mosaic for.  A TPU v5e core has 128 MiB of VMEM
# and a 16 MiB default scoped limit; the megakernels raise the limit to
# this.  The wrappers refuse a bucket whose counted working set exceeds
# VMEM_BUDGET_BYTES: compiled for v5e, Mosaic's own stack took up to 3.2 MiB
# beyond what the kernels' buffers count, so 8 MiB are kept for it.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES - 8 * 1024 * 1024


class KernelLimitError(ValueError):
    """A bucket the Pallas kernels cannot take: past the int32 offset
    range, past the VMEM limit, or a container coding with no TPU
    lowering.  Raised before dispatch; the engines never fall back to the
    XLA path on their own."""


def _coding_key(coding) -> tuple:
    """Flatten a non-trivial container-v3 coding into tuning plan-key ints.

    Trivial codings contribute NOTHING so every pre-v3 tuned entry (keyed
    without coding) keeps matching v1/v2 traffic byte-for-byte."""
    coding = tuple(coding)
    if coding == _TRIVIAL_CODING:
        return ()
    return (int(coding[0]), int(coding[1]), int(bool(coding[2])))


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp() -> bool:
    return not on_tpu()


def check_i32_offsets(num_symbols: int, max_symlen: int) -> None:
    """Refuse a decode whose dense symbol offsets would overflow int32.

    The fused kernels' compaction offsets (and the output capacity, which
    over-allocates one ``max_symlen`` row for the final word's spill) are
    int32; a bucket past the 2^31-symbol (= 2^31-byte) mark would wrap
    offsets negative and scatter symbols to the WRONG positions silently.
    Mirrors the transcoder's flat-gather int32 guard.
    """
    if int(num_symbols) + int(max_symlen) > _I32_MAX:
        raise KernelLimitError(
            f"decode bucket of {num_symbols} symbols (+{max_symlen} spill) "
            "exceeds the int32 offset range of the fused kernels — decode "
            "the archive in smaller batches"
        )


def decode_vmem_bytes(
    num_windows: int, *, n: int, e: int, max_symlen: int,
    block_words: int = _hd.BLOCK_WORDS,
    block_windows: int = _df.BLOCK_WINDOWS,
) -> int:
    """VMEM the decode megakernel needs for a bucket of ``num_windows``
    (rounded as the kernel rounds): the dense symbol scratch, the word
    tiles, the split LUT, the block-diagonal basis and the double-buffered
    output block (see :mod:`repro.kernels.decode_fused`)."""
    align = _df.window_align(e)
    bwin = -(-min(block_windows, max(num_windows, 1)) // align) * align
    nwp = -(-max(num_windows, 1) // bwin) * bwin
    lw = _df.row_lanes(e)
    rows = _hd.dense_rows(nwp * e, max_symlen)
    width = lw // e * n
    return (
        4 * _hd.LANES * rows
        + 2 * 4 * _hd.LANES * block_words
        + 2 * 2 * 4 * block_words
        + 4 * 256 * lw
        + 3 * 2 * lw * width
        + 2 * 4 * (bwin * e // lw) * width
    )


def check_decode_vmem(num_windows: int, **kw) -> None:
    """Refuse a decode bucket whose megakernel working set exceeds
    :data:`VMEM_BUDGET_BYTES` (the dense symbol stream is whole-bucket)."""
    need = decode_vmem_bytes(num_windows, **kw)
    if need > VMEM_BUDGET_BYTES:
        raise KernelLimitError(
            f"decode bucket of {num_windows} windows (E={kw['e']}) needs "
            f"{need} B of VMEM for the megakernel's dense symbol scratch; "
            f"the limit is VMEM_BUDGET_BYTES={VMEM_BUDGET_BYTES} — decode "
            "the archive in smaller batches"
        )


def _decode_blocks(
    num_words: int, num_windows: int, *, n: int, e: int, l_max: int,
    max_symlen: int, coding=_TRIVIAL_CODING,
) -> tuple:
    """``(block_words, block_windows)`` the decode megakernel runs a bucket
    with: the tuning cache's winner for this (backend, plan key, bucket
    shape), else the kernel's built-in defaults."""
    tuned = _tuned_blocks(
        "decode",
        plan_key=(n, e, l_max, max_symlen) + _coding_key(coding),
        shape=(int(num_words), int(num_windows)),
    )
    return (
        int(tuned.get("block_words", _hd.BLOCK_WORDS)),
        int(tuned.get("block_windows", _df.BLOCK_WINDOWS)),
    )


def decode_kernel_fits(
    num_words: int, num_windows: int, *, n: int, e: int, l_max: int,
    max_symlen: int, coding=_TRIVIAL_CODING,
) -> bool:
    """Whether the compiled decode megakernel takes a bucket: the checks
    :func:`decode_bucket_fused` makes on the chip (the coding, the int32
    offsets, the VMEM budget with the blocks it would run), answered
    before dispatch and without raising."""
    if tuple(coding) != _TRIVIAL_CODING:
        return False
    block_words, block_windows = _decode_blocks(
        num_words, num_windows, n=n, e=e, l_max=l_max,
        max_symlen=max_symlen, coding=coding,
    )
    try:
        check_i32_offsets(num_windows * e, max_symlen)
        check_decode_vmem(
            num_windows, n=n, e=e, max_symlen=max_symlen,
            block_words=block_words, block_windows=block_windows,
        )
    except KernelLimitError:
        return False
    return True


def check_encode_vmem(chunk_size: int, word_slots: int, lanes: int) -> None:
    """Refuse an encode bucket whose pack-kernel step exceeds the limit
    (exact-mode encodes make the whole signal one chunk)."""
    need = _ef.step_vmem_bytes(chunk_size, word_slots, lanes)
    if need > VMEM_BUDGET_BYTES:
        raise KernelLimitError(
            f"encode chunk of {chunk_size} symbols x {lanes} lanes needs "
            f"{need} B of VMEM in the pack kernel; the limit is "
            f"VMEM_BUDGET_BYTES={VMEM_BUDGET_BYTES} — encode with a smaller "
            "chunk_size"
        )


def huffman_decode(
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    symlen: jnp.ndarray,
    tables: DeviceTables,
    *,
    l_max: int,
    max_symlen: int,
    num_symbols: int,
) -> jnp.ndarray:
    """SymLen decode + compaction: packed words -> dense uint8[num_symbols].

    ONE dispatch: the symlen sidecar rides into the kernel, a running
    offset over it assigns per-word output positions, and the cooperative
    store compacts symbols inside the same ``pallas_call`` — container
    boundaries are invisible (the offsets are segment sums), so
    concatenated batch streams decode in this single dispatch with no
    ``[max_symlen, W]`` HBM tile.  ``core.symlen.compact_padded_scatter``
    (over the staged tile kernel) remains the interpret-mode oracle.
    """
    check_i32_offsets(num_symbols, max_symlen)
    dense = _hd.huffman_decode_dense(
        hi,
        lo,
        symlen,
        tables.dec_limit,
        tables.dec_first,
        tables.dec_rank,
        tables.dec_syms,
        l_max=l_max,
        max_symlen=max_symlen,
        num_symbols=num_symbols,
        interpret=_interp(),
    )
    return dense.astype(jnp.uint8)


def decode_bucket_fused(
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    symlen: jnp.ndarray,
    tables: DeviceTables,
    lut: jnp.ndarray,  # f32[E, 256] quant_grid reconstruction LUT
    rscale: jnp.ndarray,  # f32[] recon_scale of the quant table
    v3=None,  # (idx, seg) expansion arrays for non-trivial codings
    *,
    l_max: int,
    max_symlen: int,
    num_windows: int,
    n: int,
    e: int,
    coding=_TRIVIAL_CODING,
    block_words: int = None,
    block_windows: int = None,
) -> jnp.ndarray:
    """The decode megakernel: packed bucket -> windows f32[num_windows, N]
    in exactly one ``pallas_call`` (Huffman + compaction + LUT dequant +
    iDCT; see :mod:`repro.kernels.decode_fused`).

    A non-trivial ``coding`` (container v3) adds the in-kernel expansion +
    un-prediction epilogue; ``v3`` must then carry the host-built
    ``(idx, seg)`` arrays from :func:`repro.core.symlen.v3_expand_index`.
    Still exactly one ``pallas_call`` — in interpret mode; on the chip a v3
    bucket raises :class:`KernelLimitError`.

    ``block_words``/``block_windows`` default to the tuning cache's winner
    for this (backend, plan key, bucket shape) — or the kernel's built-in
    defaults when nothing is tuned.  Explicit values (the autotuner's own
    sweep path) bypass the consult."""
    check_i32_offsets(num_windows * e, max_symlen)
    coding = tuple(coding)
    interpret = _interp()
    if coding != _TRIVIAL_CODING and not interpret:
        raise KernelLimitError(
            f"container-v3 coding {coding} has no TPU lowering in the decode "
            "megakernel (its expansion epilogue gathers) — decode v3 "
            "buckets with use_kernels=False"
        )
    if block_words is None or block_windows is None:
        tuned_words, tuned_windows = _decode_blocks(
            hi.shape[0], num_windows, n=n, e=e, l_max=l_max,
            max_symlen=max_symlen, coding=coding,
        )
        if block_words is None:
            block_words = tuned_words
        if block_windows is None:
            block_windows = tuned_windows
    if not interpret:
        check_decode_vmem(
            num_windows, n=n, e=e, max_symlen=max_symlen,
            block_words=int(block_words), block_windows=int(block_windows),
        )
    idx, seg = v3 if v3 is not None else (None, None)
    return _df.decode_fused(
        hi,
        lo,
        symlen,
        tables.dec_limit,
        tables.dec_first,
        tables.dec_rank,
        tables.dec_syms,
        lut,
        rscale,
        idx,
        seg,
        l_max=l_max,
        max_symlen=max_symlen,
        num_windows=num_windows,
        n=n,
        e=e,
        coding=coding,
        block_words=int(block_words),
        block_windows=int(block_windows),
        vmem_limit_bytes=None if interpret else VMEM_LIMIT_BYTES,
        interpret=interpret,
    )


def encode_bucket_fused(
    syms: jnp.ndarray,  # int32[K, Sp] symbols per signal row
    valid: jnp.ndarray,  # bool[K, Sp] slots that pack
    tables: DeviceTables,
    *,
    chunk_size: int,
    l_max: int,
    block_lanes: int = None,
):
    """The encode entropy stage in one ``pallas_call``: a bucket's symbol
    rows -> SymLen chunk parts ``(hi uint32[K, B, C], lo, symlen int32[K,
    B, C], words_per_chunk int32[K, B])``, byte-identical to ``vmap`` of
    :func:`repro.core.symlen.pack_symlen_chunked_parts` over the rows (see
    :mod:`repro.kernels.encode_fused`).

    ``block_lanes`` (chunks packed per grid step) defaults to the tuning
    cache's winner for this (backend, plan key, bucket shape), falling back
    to :data:`~repro.kernels.encode_fused.BLOCK_LANES`; explicit values
    bypass the consult (the autotuner's sweep path)."""
    k, sp = syms.shape
    c = int(chunk_size)
    b = max(-(-sp // c), 1)
    words = chunk_words_bound(c, l_max)
    if block_lanes is None:
        tuned = _tuned_blocks(
            "encode", plan_key=(int(l_max), c), shape=(int(k), int(sp)),
        )
        block_lanes = tuned.get("block_lanes", _ef.BLOCK_LANES)
    interpret = _interp()
    if not interpret:
        check_encode_vmem(c, words, min(int(block_lanes), k * b))
    # slot-major chunk layout: [K, Sp] -> [K*B, C] -> [C, K*B]
    s = jnp.where(valid, syms.astype(jnp.int32), -1)
    s = jnp.pad(s, ((0, 0), (0, b * c - sp)), constant_values=-1)
    hi, lo, sl, wpc = _ef.encode_pack(
        s.reshape(k * b, c).T,
        tables.codes,
        tables.lengths,
        word_slots=words,
        block_lanes=int(block_lanes),
        vmem_limit_bytes=None if interpret else VMEM_LIMIT_BYTES,
        interpret=interpret,
    )

    def parts(x):  # [words, K*B] -> [K, B, C], zero word slots past words
        x = jnp.pad(x.T, ((0, 0), (0, c - words)))
        return x.reshape(k, b, c)

    return parts(hi), parts(lo), parts(sl), wpc.reshape(k, b)


def idct_dequant(
    levels: jnp.ndarray,
    lut: jnp.ndarray,  # f32[E, 256] quant_grid reconstruction LUT
    rscale: jnp.ndarray,  # f32[] recon_scale of the quant table
    *,
    n: int,
) -> jnp.ndarray:
    """Fused LUT dequant + inverse DCT: [W, E] levels -> [W, N] samples."""
    return _idq.idct_dequant(levels, lut, rscale, n=n, interpret=_interp())


def dct_quant(
    windows: jnp.ndarray,
    quant: QuantTable,
    *,
    e: int,
    basis: jnp.ndarray = None,
    exact: bool = False,
) -> jnp.ndarray:
    """Fused forward DCT + quantize: [W, N] samples -> [W, E] levels.

    ``basis`` lets callers with a persistent encode plan pass the
    already-device-resident DCT basis (used by the approximate arm);
    ``exact=True`` selects the reference-parity arm — the backend-exact
    split DCT and the threshold quantizer traced in-kernel, bit-identical
    levels to ``core.quantize.quantize(core.dct.forward_dct(...))`` (what
    the fixed-rate workload path pins its byte-identity tests on).
    """
    n = windows.shape[-1]
    if basis is None:
        basis = _dct.dct_basis(n, e)
    return _dq.dct_quant(
        windows,
        quant,
        basis,
        e=e,
        interpret=_interp(),
        exact=exact,
    )
