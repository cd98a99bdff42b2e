"""The plain reference codec: FPTC written out from the paper's equations.

It is the yardstick that decides ``correct``, so it imports nothing of the
system under test and takes none of its tables: it builds its own Huffman
code from the histogram the benchmark calibrated, its own quantiser cells
and reconstruction grid from the shipped scales, its own DCT bases in
float64, and parses the container wire format itself.

  * wire format (v1/v2): a 48-byte little-endian header (magic ``FPTC``,
    version, l_max, n, e, num_words, num_symbols, num_windows,
    signal_length, max_symlen, domain_id, crc32), then the 64-bit words and
    the one-byte symbol-count sidecar; v2's crc covers words || sidecar;
  * SymLen words: whole canonical Huffman codes packed from the most
    significant bit, ``symlen[w]`` codes in word ``w``;
  * Huffman: length-limited (package-merge, leaves before packages at equal
    weight, leaves in symbol order), canonical by (length, symbol);
  * quantiser (paper Eqs. 2-3): level 128 is zero; zone 0 mu-law with 126
    steps up (levels 129..255) and 127 down (127..0), a first step for any
    c > 0; zone 1 linear beyond a deadzone d1 = alpha1 * A; zone 2 zero;
    reconstruction at each cell's midpoint value;
  * transform: DCT-II ``C[k] = 2/N sum x[n] cos(pi/N (n + 1/2) k)`` over
    non-overlapping windows, inverse DCT-III with the DC term halved, the
    first E coefficients kept.
"""
from __future__ import annotations

import dataclasses
import struct
import zlib
from typing import Tuple

import numpy as np

__all__ = [
    "RefTables",
    "package_merge",
    "canonical_lut",
    "parse",
    "huffman_decode",
    "dct_basis",
    "idct_basis",
    "windows_of",
    "quantise",
    "decode_levels",
    "sample_gap",
    "level_miss",
    "reconstruct",
]

_HDR = struct.Struct("<4sHHHHIQIQHHI")
_TINY = float(np.finfo(np.float32).tiny)


class RefFormatError(ValueError):
    """A container that the reference cannot read."""


@dataclasses.dataclass(frozen=True)
class RefTables:
    """One domain's shipped tables as the reference holds them."""

    domain_id: int
    n: int
    e: int
    l_max: int
    b1: int
    b2: int
    mu: float
    alpha1: float
    scale: np.ndarray  # float64[E], the shipped float32 scales
    hist: np.ndarray  # int64[256], the smoothed calibration histogram

    @property
    def zone(self) -> np.ndarray:
        z = np.full(self.e, 2)
        z[: self.b2] = 1
        z[: self.b1] = 0
        return z

    def lengths(self) -> np.ndarray:
        return _cached(self, "_lengths", lambda: package_merge(self.hist, self.l_max))

    def lut(self) -> Tuple[np.ndarray, np.ndarray]:
        return _cached(self, "_lut", lambda: canonical_lut(self.lengths(), self.l_max))

    def edges(self) -> np.ndarray:
        """float64[E, 257]: level L of band k holds ``edges[k, L] <= c <
        edges[k, L+1]``."""
        return _cached(self, "_edges", lambda: _edges(self))

    def grid(self) -> np.ndarray:
        """float64[E, 256]: the reconstruction value of every level."""
        return _cached(self, "_grid", lambda: _grid(self))


def _cached(obj, name, make):
    v = obj.__dict__.get(name)
    if v is None:
        v = make()
        object.__setattr__(obj, name, v)
    return v


# ---------------------------------------------------------------------------
# Huffman
# ---------------------------------------------------------------------------
def package_merge(freqs: np.ndarray, l_max: int) -> np.ndarray:
    """Length-limited optimal code lengths (Larmore-Hirschberg)."""
    freqs = np.asarray(freqs, dtype=np.int64)
    active = np.flatnonzero(freqs > 0)
    n = active.size
    lengths = np.zeros(freqs.size, dtype=np.int64)
    if n == 1:
        lengths[active] = 1
    if n <= 1:
        return lengths
    order = np.argsort(freqs[active], kind="stable")
    leaves = [(int(freqs[active[i]]), np.eye(1, n, i, dtype=np.int64)[0])
              for i in order]
    items = list(leaves)
    for _ in range(l_max - 1):
        pkgs = [(items[i][0] + items[i + 1][0], items[i][1] + items[i + 1][1])
                for i in range(0, len(items) - 1, 2)]
        # a stable merge by weight: leaves first among equal weights
        items = sorted(leaves + pkgs, key=lambda t: t[0])
    depth = np.sum([c for _, c in items[: 2 * n - 2]], axis=0)
    lengths[active] = depth
    return lengths


def canonical_lut(lengths: np.ndarray, l_max: int):
    """(symbol, length) per l_max-bit prefix of the canonical code."""
    lengths = np.asarray(lengths)
    syms = sorted((int(lengths[s]), s) for s in range(lengths.size) if lengths[s])
    lut_sym = np.zeros(1 << l_max, dtype=np.uint8)
    lut_len = np.zeros(1 << l_max, dtype=np.uint64)
    code, prev = 0, 0
    for ln, s in syms:
        code <<= ln - prev
        prev = ln
        lo = code << (l_max - ln)
        lut_sym[lo: lo + (1 << (l_max - ln))] = s
        lut_len[lo: lo + (1 << (l_max - ln))] = ln
        code += 1
    if code != (1 << prev):
        raise ValueError("code lengths do not form a complete prefix code")
    return lut_sym, lut_len


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Parsed:
    version: int
    l_max: int
    n: int
    e: int
    num_symbols: int
    num_windows: int
    signal_length: int
    domain_id: int
    words: np.ndarray  # uint64[W]
    symlen: np.ndarray  # uint8[W]


def parse(blob: bytes) -> Parsed:
    if len(blob) < _HDR.size:
        raise RefFormatError("shorter than the header")
    (magic, version, l_max, n, e, nw, nsym, nwin, slen, _maxsl, dom,
     crc) = _HDR.unpack_from(blob, 0)
    if magic != b"FPTC" or version not in (1, 2):
        raise RefFormatError(f"magic {magic!r} version {version}")
    need = _HDR.size + 9 * nw
    if len(blob) != need:
        raise RefFormatError(f"{len(blob)} bytes, header says {need}")
    wb = blob[_HDR.size: _HDR.size + 8 * nw]
    sb = blob[_HDR.size + 8 * nw:]
    want = zlib.crc32(sb) if version == 1 else zlib.crc32(sb, zlib.crc32(wb))
    if want != crc:
        raise RefFormatError("crc mismatch")
    return Parsed(version, l_max, n, e, nsym, nwin, slen, dom,
                  np.frombuffer(wb, "<u8"), np.frombuffer(sb, np.uint8))


def huffman_decode(words: np.ndarray, symlen: np.ndarray, tables: RefTables,
                   num_symbols: int) -> np.ndarray:
    """Decode SymLen words, one slot of every word at a time."""
    lut_sym, lut_len = tables.lut()
    lmax = np.uint64(tables.l_max)
    mask = np.uint64((1 << tables.l_max) - 1)
    cur = np.array(words, dtype=np.uint64)
    sl = symlen.astype(np.int64)
    width = int(sl.max()) if sl.size else 0
    out = np.zeros((cur.size, width), dtype=np.uint8)
    used = np.zeros(cur.size, dtype=np.uint64)
    for j in range(width):
        live = j < sl
        prefix = (cur >> (np.uint64(64) - lmax)) & mask
        out[:, j] = lut_sym[prefix]
        ln = np.where(live, lut_len[prefix], np.uint64(0))
        used += ln
        cur = cur << ln
    if np.any(used > 64):
        raise RefFormatError("a word holds more than 64 bits of codes")
    syms = out[np.arange(width)[None, :] < sl[:, None]]
    if syms.size != num_symbols:
        raise RefFormatError(f"{syms.size} symbols, header says {num_symbols}")
    return syms


# ---------------------------------------------------------------------------
# Transform and quantiser
# ---------------------------------------------------------------------------
def dct_basis(n: int, e: int) -> np.ndarray:
    """[N, E]: coefficients = windows @ basis."""
    x = np.arange(n)[:, None] + 0.5
    k = np.arange(e)[None, :]
    return (2.0 / n) * np.cos(np.pi / n * x * k)


def idct_basis(n: int, e: int) -> np.ndarray:
    """[E, N]: windows = coefficients @ basis."""
    b = np.cos(np.pi / n * (np.arange(n)[None, :] + 0.5) * np.arange(e)[:, None])
    b[0] *= 0.5
    return b


def windows_of(signal: np.ndarray, n: int) -> np.ndarray:
    x = np.asarray(signal, dtype=np.float64).ravel()
    w = -(-x.size // n)
    out = np.zeros(w * n)
    out[: x.size] = x
    return out.reshape(w, n)


def _edges(t: RefTables) -> np.ndarray:
    e = t.e
    edges = np.empty((e, 257))
    edges[:, 0], edges[:, 256] = -np.inf, np.inf
    lmu = np.log1p(t.mu)
    up_q = (np.arange(1, 127) - 0.5) / 126.0
    dn_q = (np.arange(1, 128) - 0.5) / 127.0
    for k, z in enumerate(t.zone):
        a = t.scale[k]
        if z == 0:
            up = np.concatenate([[_TINY], a * np.expm1(up_q * lmu) / t.mu])
            dn = np.concatenate([[_TINY], a * np.expm1(dn_q * lmu) / t.mu])
        elif z == 1:
            d1 = t.alpha1 * a
            up = np.concatenate([[d1], d1 + up_q * (a - d1)])
            dn = np.concatenate([[d1], d1 + dn_q * (a - d1)])
        else:
            up = np.full(127, np.inf)
            dn = np.full(128, np.inf)
        up = np.where(up > a, np.inf, up)
        dn = np.where(dn > a, np.inf, dn)
        edges[k, 1:129] = -dn[::-1]
        edges[k, 129:256] = up
    return edges


def _grid(t: RefTables) -> np.ndarray:
    lvl = np.arange(256.0)[None, :]
    a = t.scale[:, None]
    q = np.clip(np.where(lvl > 128, (lvl - 129) / 126.0, (127 - lvl) / 127.0),
                0.0, 1.0)
    sign = np.sign(lvl - 128)
    d1 = t.alpha1 * a
    z = t.zone[:, None]
    mag = np.where(z == 0, a * np.expm1(q * np.log1p(t.mu)) / t.mu,
                   np.where(z == 1, d1 + q * (a - d1), 0.0))
    return sign * mag


def quantise(coeffs: np.ndarray, t: RefTables) -> np.ndarray:
    """Levels [W, E] of float64 coefficients [W, E]."""
    edges = t.edges()
    out = np.empty(coeffs.shape, dtype=np.int64)
    for k in range(t.e):
        out[:, k] = np.searchsorted(edges[k], coeffs[:, k], side="right") - 1
    return np.clip(out, 0, 255)


def decode_levels(blob: bytes, t: RefTables) -> Tuple[Parsed, np.ndarray]:
    """Parse a container and return its level grid [num_windows, E]."""
    p = parse(blob)
    if (p.n, p.e, p.l_max, p.domain_id) != (t.n, t.e, t.l_max, t.domain_id):
        raise RefFormatError(
            f"container (n, e, l_max, domain)={(p.n, p.e, p.l_max, p.domain_id)}"
            f" does not match tables {(t.n, t.e, t.l_max, t.domain_id)}")
    if p.num_symbols != p.num_windows * p.e:
        raise RefFormatError("symbol count is not windows * E")
    if not (p.num_windows - 1) * p.n < p.signal_length <= p.num_windows * p.n:
        raise RefFormatError("signal length does not match the window count")
    syms = huffman_decode(p.words, p.symlen, t, p.num_symbols)
    return p, syms.reshape(p.num_windows, p.e)


def reconstruct(levels: np.ndarray, t: RefTables, length: int) -> np.ndarray:
    """float64 samples of a level grid [W, E]."""
    coeffs = t.grid()[np.arange(t.e)[None, :], levels.astype(np.int64)]
    return (coeffs @ idct_basis(t.n, t.e)).ravel()[:length]


def decode(blob: bytes, t: RefTables) -> np.ndarray:
    p, levels = decode_levels(blob, t)
    return reconstruct(levels, t, p.signal_length)


def sample_gap(got: np.ndarray, want: np.ndarray, t: RefTables) -> float:
    """The largest sample difference over the domain's largest band scale
    A (the bound on every reconstructed coefficient): float32 arithmetic
    on coefficients up to A errs in proportion to A."""
    return float(np.max(np.abs(got - want), initial=0.0)) / float(t.scale.max())


def level_miss(got: np.ndarray, levels: np.ndarray, t: RefTables) -> Tuple[int, int]:
    """(misses, compared): coefficients of ``got``'s whole windows, taken
    back through the float64 DCT, whose nearest reconstruction value in
    their band is not the value of the reference's ``levels`` [W, E]."""
    w = min(np.asarray(got).size // t.n, levels.shape[0])
    c = coefficients(np.asarray(got).ravel()[: w * t.n], t.n, t.e)
    grid = t.grid()
    want = grid[np.arange(t.e)[None, :], levels[:w].astype(np.int64)]
    misses = 0
    for k in range(t.e):
        vals = np.unique(grid[k])
        j = np.clip(np.searchsorted(vals, c[:, k]), 1, max(vals.size - 1, 1))
        lo = vals[j - 1]
        hi = vals[np.minimum(j, vals.size - 1)]
        near = np.where(np.abs(c[:, k] - lo) <= np.abs(hi - c[:, k]), lo, hi)
        misses += int(np.count_nonzero(near != want[:, k]))
    return misses, w * t.e


def coefficients(signal: np.ndarray, n: int, e: int) -> np.ndarray:
    return windows_of(signal, n) @ dct_basis(n, e)

