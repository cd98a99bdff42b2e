"""The work a bucket program must do, counted from live container shapes.

Counts follow the algorithm, not an implementation: they read the
containers' live words, windows, N and E, never a padded bucket, so the
same call counts the same work whether XLA or a Pallas kernel runs it.

Decode of one container (Huffman decode, dequantisation, inverse DCT):
  bytes  = 8 * words + words      (the SymLen words and the u8 sidecar)
         + 4 * windows * N        (float32 samples written)
  flops  = 2 * windows * E * N    (the inverse DCT's multiply-adds)
Tables (a few KiB) are left out.  The least time is the larger of
flops / peak FLOP/s (bf16, the MXU's rate) and bytes / peak HBM bytes/s.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

__all__ = ["decode_work", "least_time", "total"]


def decode_work(words: int, windows: int, n: int, e: int) -> Tuple[float, float]:
    """(flops, bytes) of decoding one container."""
    return 2.0 * windows * e * n, 9.0 * words + 4.0 * windows * n


def least_time(flops: float, nbytes: float, peaks: Dict[str, float]):
    """(seconds, bound) — the larger of the compute and memory times."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c > t_m else (t_m, "memory")


def total(items: Iterable[Tuple[float, float]]) -> Tuple[float, float]:
    f = b = 0.0
    for fi, bi in items:
        f += fi
        b += bi
    return f, b
