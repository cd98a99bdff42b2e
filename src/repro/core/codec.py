"""End-to-end FPTC codec (paper Fig. 3): transform → quantize → entropy code.

Two matched implementations:

  * **Host path** (`encode` / `decode`) — numpy + Algorithm-1 reference
    bitpacking.  This models the paper's embedded sequential encoder and
    serves as the oracle for everything else.
  * **Device path** (`encode_device` / `decode_device`) — jitted JAX.  The
    decoder is the word-parallel SymLen decode + fused dequant/iDCT pipeline
    (the paper's dual-fused GPU design, lifted to XLA; the Pallas kernels in
    ``repro.kernels`` are the hand-tiled TPU versions wired in via
    ``use_kernels=True``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dct, symlen
from repro.core.calibration import DeviceTables, DomainTables
from repro.core.container import Container
from repro.core.quantize import (
    dequantize,
    expand_coded_stream,
    predict_levels,
    quant_grid,
    quantize,
    recon_scale,
    unpredict_levels,
)

__all__ = [
    "encode",
    "decode",
    "encode_device",
    "decode_device",
    "transcode",
    "validate_container_tables",
]


def validate_container_tables(plan_key, tables: DomainTables) -> None:
    """Reject a container/tables pairing whose configs disagree.

    A container carries its encode-time (domain_id, n, e, l_max, coding) in
    the header; decoding it with a :class:`DomainTables` built for a
    different config either dies in an opaque shape error or — worse —
    decodes silently to garbage (coincident config, different book: two
    domains can share (n, e, l_max) yet quantize/code differently, so
    domain_id is part of the check; a v3 book is calibrated on *coded*
    residual symbols, so the coding triple is too).  Every decode path calls
    this before touching the stream.
    """
    cfg = tables.config
    want = (tables.domain_id, cfg.n, cfg.e, cfg.l_max, cfg.coding)
    if tuple(plan_key) != want:
        raise ValueError(
            f"container plan_key (domain_id, n, e, l_max, coding)="
            f"{tuple(plan_key)} does not match the supplied DomainTables "
            f"{want} — decoding with mismatched tables would produce garbage"
        )


# ---------------------------------------------------------------------------
# Host (reference / embedded-encoder) path
# ---------------------------------------------------------------------------
def encode(signal: np.ndarray, tables: DomainTables) -> Container:
    """Single-pass table-driven encode (paper §4.1, Fig. 5).

    With a v3 coding in the config, the quantized level grid is re-coded
    losslessly before entropy coding: prediction residuals on the low bands
    (``quantize.predict_levels``) and zero-plane suppression
    (``symlen.zero_plane_masks``); the container records both in its header.
    """
    cfg = tables.config
    pred_id, bands, zplanes = cfg.coding
    signal = np.asarray(signal, dtype=np.float32).ravel()
    length = signal.shape[0]
    windows = dct.window_signal(jnp.asarray(signal), cfg.n)
    coeffs = dct.forward_dct(windows, cfg.e)
    levels = quantize(coeffs, tables.quant)
    grid = np.asarray(predict_levels(levels, pred_id, bands))
    zrow = zcol = None
    if zplanes:
        zrow, zcol = symlen.zero_plane_masks(grid)
        coded = grid[~zrow, :][:, ~zcol].ravel()
    else:
        coded = grid.ravel()
    stream = symlen.pack_symlen_np(coded, tables.book)
    return Container(
        words=stream.words,
        symlen=stream.symlen.astype(np.uint8),
        num_symbols=stream.num_symbols,
        num_windows=int(windows.shape[0]),
        signal_length=length,
        n=cfg.n,
        e=cfg.e,
        l_max=cfg.l_max,
        domain_id=tables.domain_id,
        predictor=pred_id,
        predict_bands=bands,
        zero_planes=zplanes,
        zrow=zrow,
        zcol=zcol,
    )


def decode(container: Container, tables: DomainTables) -> np.ndarray:
    """Reference decode: serial Huffman LUT + dequant + inverse DCT."""
    validate_container_tables(container.plan_key, tables)
    stream = symlen.PackedStream(
        words=container.words,
        symlen=container.symlen.astype(np.int32),
        num_symbols=container.num_symbols,
    )
    syms = symlen.unpack_symlen_np(stream, tables.book)
    pred_id, bands, zplanes = container.coding
    nw, e = container.num_windows, container.e
    if container.coding == (0, 0, False):
        coeffs_q = jnp.asarray(syms.reshape(nw, e))
    else:
        idx, seg = symlen.v3_expand_index(
            [(nw, container.zrow, container.zcol)], e
        )
        if syms.size == 0:  # everything suppressed: the grid is all 128
            grid = np.full((nw, e), 128, dtype=np.int32)
        else:
            grid = np.asarray(
                expand_coded_stream(
                    jnp.asarray(syms, jnp.int32), jnp.asarray(idx)
                )
            ).reshape(nw, e)
        coeffs_q = unpredict_levels(
            jnp.asarray(grid, jnp.uint32), jnp.asarray(seg), pred_id, bands
        )
    coeffs = dequantize(coeffs_q, tables.quant)
    windows = dct.inverse_dct(
        coeffs, container.n, scale=recon_scale(tables.quant)
    )
    return np.asarray(dct.unwindow_signal(windows, container.signal_length))


# ---------------------------------------------------------------------------
# Device (jitted) path
# ---------------------------------------------------------------------------
# Legacy per-signal encode jit: length-S serial packing scan, one XLA
# specialization per signal length, and a blocking int(num_words) sync per
# container.  Kept ONLY as the baseline the batched encode engine is
# benchmarked against (bench_throughput) — production callers go through
# encode_device -> serving.batch_encode.
@functools.partial(jax.jit, static_argnames=("n", "e"))
def _encode_stages_device(
    signal: jnp.ndarray, tables: DeviceTables, n: int, e: int
):
    windows = dct.window_signal(signal, n)
    coeffs = dct.forward_dct(windows, e)
    syms = quantize(coeffs, tables.quant).ravel()
    hi, lo, sl, num_words = symlen.pack_symlen_scan(
        syms, tables.codes, tables.lengths
    )
    return hi, lo, sl, num_words, windows.shape[0]


def encode_device(
    signal: jnp.ndarray, tables: DomainTables
) -> Container:
    """Jitted encode, bit-identical to the host encoder.

    Batch-of-one wrapper over the bucketed batch engine
    (:mod:`repro.serving.batch_encode`) in exact packing mode: tables ride
    the persistent plan cache, shapes ride power-of-two buckets, and the
    only *output* sync is the batch drain (no per-container
    ``int(num_words)`` inside the jitted hot path).  Note the engine stages
    inputs through host buffers for bucket stacking, so a device-resident
    input array costs one device->host transfer here — ingest inputs are
    host arrays in the intended deployment.  Encode many signals at once —
    and get chunk-parallel packing — with
    :class:`repro.serving.batch_encode.BatchEncoder` directly.
    """
    from repro.serving.batch_encode import default_encoder

    return default_encoder().encode([signal], tables).to_host()[0]


# Legacy per-container jit: every shape-ish quantity is a static argname, so
# a heterogeneous archive retraces XLA per container.  Kept ONLY as the
# baseline the batched engine is benchmarked against (bench_throughput) —
# production callers go through decode_device -> serving.batch_decode.
@functools.partial(
    jax.jit,
    static_argnames=("l_max", "max_symlen", "num_symbols", "num_windows",
                     "n", "e", "signal_length", "use_kernels"),
)
def _decode_device(
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    sl: jnp.ndarray,
    tables: DeviceTables,
    *,
    l_max: int,
    max_symlen: int,
    num_symbols: int,
    num_windows: int,
    n: int,
    e: int,
    signal_length: int,
    use_kernels: bool = False,
) -> jnp.ndarray:
    if use_kernels:
        # hand-tiled Pallas TPU kernels (interpret=True on CPU)
        from repro.kernels import ops as kops

        syms = kops.huffman_decode(
            hi, lo, sl, tables,
            l_max=l_max, max_symlen=max_symlen, num_symbols=num_symbols,
        )
        coeffs_q = syms.reshape(num_windows, e)
        lut, _ = quant_grid(tables.quant)
        windows = kops.idct_dequant(
            coeffs_q, lut, recon_scale(tables.quant), n=n
        )
    else:
        syms = symlen.unpack_symlen(
            hi, lo, sl,
            tables.dec_limit, tables.dec_first, tables.dec_rank,
            tables.dec_syms,
            l_max=l_max, max_symlen=max_symlen, num_symbols=num_symbols,
        )
        coeffs_q = syms.reshape(num_windows, e)
        coeffs = dequantize(coeffs_q, tables.quant)
        windows = dct.inverse_dct(coeffs, n, scale=recon_scale(tables.quant))
    return dct.unwindow_signal(windows, signal_length)


def decode_device(
    container: Container,
    tables: DomainTables,
    *,
    use_kernels: Optional[bool] = None,
) -> np.ndarray:
    """Word-parallel decode (the paper's dual-fused GPU pipeline on XLA/TPU).

    Batch-of-one wrapper over the bucketed batch engine
    (:mod:`repro.serving.batch_decode`): shape buckets bound recompilation,
    tables/bases ride the persistent plan cache.  ``use_kernels`` selects
    the fused Pallas megakernel path (``None`` defers to the process-wide
    ``FPTC_USE_KERNELS`` default; the kernel path is bit-identical to the
    XLA path).  Decode many containers at once with
    :class:`repro.serving.batch_decode.BatchDecoder` directly.
    """
    from repro.serving.batch_decode import default_decoder

    dec = default_decoder(use_kernels=use_kernels)
    return dec.decode([container], tables).to_host()[0]


def transcode(
    container: Container,
    src_tables: DomainTables,
    dst_tables: DomainTables,
) -> Container:
    """Re-encode one container under a new (domain, config), device-resident.

    Container-of-one wrapper over the transcode pipeline
    (:mod:`repro.serving.transcode`) in exact packing mode: decode and
    re-encode compose on device with no host round trip in between, and the
    output is byte-identical to ``decode_device``-to-host followed by
    ``encode_device`` under ``dst_tables``.  Transcode many containers at
    once — and get chunk-parallel packing — with
    :class:`repro.serving.transcode.Transcoder` directly.
    """
    from repro.serving.transcode import default_transcoder

    return default_transcoder().transcode_to_host(
        [container], src_tables, dst_tables
    )[0]


def roundtrip_metrics(
    signal: np.ndarray, tables: DomainTables
) -> Tuple[float, float]:
    """(CR, PRD) of a host-path roundtrip — used by RD benchmarks."""
    from repro.core.metrics import prd

    c = encode(signal, tables)
    rec = decode(c, tables)
    return c.compression_ratio, prd(signal, rec)
