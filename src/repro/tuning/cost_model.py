"""Per-device dispatch cost model for the serving engines' shape decisions.

The model predicts the wall cost of one fused bucket dispatch from
(words, windows, batch, block sizes, backend) with a plain roofline:

    t = flops / peak_flops + bytes / hbm_bps
        + grid_steps * step_overhead_s + dispatch_overhead_s

The analytic flop/byte counts mirror what the kernels actually trace (the
slot-loop Huffman decode, the 256-level LUT select, the MXU iDCT / DCT,
the one-hot codeword matmul, the chunk pack) and can be *seeded* — rescaled
so the analytic count matches an :func:`repro.analysis.analyze_hlo` /
:func:`repro.analysis.analyze_jaxpr` (or XLA ``cost_analysis()``) estimate
of the real traced program — and *refined* by on-device timing samples
(:meth:`CostModel.observe`; the autotuner feeds these automatically).

Three consumers:

  * :func:`repro.tuning.policy.cost_balanced_policy` picks the bucket-edge
    density where the padded-work saving of a denser ladder stops paying
    for its extra jit specializations;
  * ``serving.engine.BucketScheduler`` splits each key group's members into
    per-device shards balanced by :meth:`CostModel.signal_decode_cost` /
    :meth:`signal_encode_cost` instead of equal counts;
  * :func:`repro.tuning.autotune.tune` ranks candidate megakernel block
    sizes with :meth:`decode_bucket_cost` / :meth:`encode_bucket_cost`
    before (or instead of) timing them.

All numbers are *relative* by design — shard balancing and candidate
ranking only need ordering, and the seeding/calibration hooks tighten the
absolute scale where it matters.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Dict, Optional, Tuple

__all__ = ["BackendProfile", "CostModel", "PEAKS", "default_cost_model"]


@dataclasses.dataclass(frozen=True)
class BackendProfile:
    """Roofline numbers for one device kind.

    ``peak_flops``/``hbm_bps`` are the device's published peaks (the
    ``source`` says where from); the three overheads are coarse launch and
    compile costs that set the *ratios* the policy and tuner decisions
    depend on, and timing calibration absorbs the rest.
    """

    device_kind: str
    peak_flops: float  # FLOP/s
    hbm_bps: float  # bytes/s
    dispatch_overhead_s: float  # per fused dispatch (host->device launch)
    step_overhead_s: float  # per grid step inside a kernel
    compile_cost_s: float  # per new jit specialization
    source: str


_V5E = dict(
    peak_flops=197e12, hbm_bps=819e9, dispatch_overhead_s=2e-6,
    step_overhead_s=2e-8, compile_cost_s=1.0,
    source="Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
    "819 GB/s HBM per chip",
)

# Peaks by ``jax.devices()[0].device_kind``.  A device kind that is not
# here is an error, never a default: a wrong roofline silently skews every
# shard split and tuner ranking built on it.
PEAKS: Dict[str, BackendProfile] = {
    "cpu": BackendProfile(
        "cpu", 5e10, 2e10, 3e-5, 2e-7, 0.5,
        source="order-of-magnitude server CPU figures (test backend)",
    ),
    "TPU v5 lite": BackendProfile("TPU v5 lite", **_V5E),  # TPU v5e
}


def device_profile(device_kind: Optional[str] = None) -> BackendProfile:
    """The :data:`PEAKS` entry for ``device_kind`` (default: the first
    visible device's); raises ``KeyError`` for a kind not in the table."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no roofline peaks for device kind {device_kind!r}; add its "
            f"published figures to repro.tuning.cost_model.PEAKS (known: "
            f"{sorted(PEAKS)})"
        ) from None


# analytic per-unit op counts, mirroring the traced kernels:
#   huffman slot step: ~l_max compare/shift ops per (word, slot) iteration
_HUFFMAN_OPS_PER_SLOT = 16.0
#   LUT dequant: the fused kernel's 256-way masked select per level
_LUT_OPS_PER_LEVEL = 256.0
#   chunk pack: segment-sum + searchsorted word materialization per symbol
_PACK_OPS_PER_SYMBOL = 24.0


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // max(int(b), 1))


def _round_up(a: int, b: int) -> int:
    return _ceil_div(a, b) * max(int(b), 1)


class CostModel:
    """Predicts fused-dispatch cost; thread-safe (engines share one).

    ``seed(kind, flops, hbm_bytes, **shape)`` rescales the analytic model
    so its raw counts reproduce a measured estimate of the same shape;
    ``observe(kind, predicted_s, measured_s)`` records a wall-time sample
    whose running median multiplies later predictions of that kind.
    """

    def __init__(
        self,
        profile: Optional[BackendProfile] = None,
        *,
        device_kind: Optional[str] = None,
    ):
        if profile is None:
            profile = device_profile(device_kind)
        self.profile = profile
        self._lock = threading.Lock()
        # kind -> (flops scale, bytes scale) from HLO/jaxpr seeding
        self._seed: Dict[str, Tuple[float, float]] = {}
        # kind -> measured/predicted wall-time ratios (bounded history)
        self._samples: Dict[str, deque] = {}

    # -- analytic op counts -------------------------------------------------
    def decode_flops(
        self,
        words: int,
        windows: int,
        *,
        e: int,
        n: int,
        max_symlen: int = 8,
    ) -> float:
        """Raw FLOP count of one fused bucket decode: slot-loop Huffman
        over the words, 256-level LUT dequant and the iDCT matmul over the
        windows (padding words/windows pay full price — that is the point:
        the model sees the cost of a policy's padding)."""
        huff = float(words) * max(max_symlen, 1) * _HUFFMAN_OPS_PER_SLOT
        dequant = float(windows) * e * _LUT_OPS_PER_LEVEL
        idct = 2.0 * float(windows) * e * n
        return huff + dequant + idct

    def decode_bytes(
        self, words: int, windows: int, *, e: int, n: int
    ) -> float:
        """Boundary HBM traffic of one bucket decode: the packed words
        (hi/lo/symlen, 12 B each) in, the window tensor out."""
        return 12.0 * float(words) + 4.0 * float(windows) * n

    def encode_flops(
        self, rows: int, windows_per_row: int, *, e: int, n: int
    ) -> float:
        """Raw FLOP count of one fused bucket encode: DCT matmul, the
        one-hot codeword lookup matmuls and the chunk pack, all over the
        padded ``rows x windows_per_row`` bucket."""
        syms = float(rows) * windows_per_row * e
        dct = 2.0 * float(rows) * windows_per_row * n * e
        onehot = 2.0 * syms * 256.0 * 2.0  # code + length lookup matmuls
        pack = syms * _PACK_OPS_PER_SYMBOL
        return dct + onehot + pack

    def encode_bytes(
        self, rows: int, windows_per_row: int, *, e: int, n: int
    ) -> float:
        samples_in = 4.0 * float(rows) * windows_per_row * n
        words_out = 12.0 * float(rows) * windows_per_row * e / 4.0
        return samples_in + words_out

    # -- seeding / calibration ---------------------------------------------
    def seed(
        self, kind: str, flops: float, hbm_bytes: float, **shape
    ) -> None:
        """Rescale the analytic model so its raw counts for ``shape``
        reproduce a measured (HLO / jaxpr / ``cost_analysis()``) estimate.

        ``kind`` is ``"decode"`` or ``"encode"``; ``shape`` carries the
        same keywords the corresponding ``*_flops`` method takes.
        """
        if kind == "decode":
            raw_f = self.decode_flops(**shape)
            raw_b = self.decode_bytes(
                **{k: v for k, v in shape.items() if k != "max_symlen"}
            )
        elif kind == "encode":
            raw_f = self.encode_flops(**shape)
            raw_b = self.encode_bytes(**shape)
        else:
            raise ValueError(f"unknown cost kind {kind!r}")
        with self._lock:
            self._seed[kind] = (
                flops / max(raw_f, 1.0),
                hbm_bytes / max(raw_b, 1.0),
            )

    def seed_from_cost(self, kind: str, cost, **shape) -> None:
        """Seed from an :class:`repro.analysis.HloCost` (what
        ``analyze_hlo``/``analyze_jaxpr`` return)."""
        self.seed(kind, cost.flops, cost.hbm_bytes, **shape)

    def observe(self, kind: str, predicted_s: float, measured_s: float):
        """Record one on-device timing sample for ``kind``; the running
        median of measured/predicted multiplies later predictions."""
        if predicted_s <= 0 or measured_s <= 0:
            return
        with self._lock:
            self._samples.setdefault(kind, deque(maxlen=64)).append(
                measured_s / predicted_s
            )

    def calibration(self, kind: str) -> float:
        with self._lock:
            samples = sorted(self._samples.get(kind, ()))
        if not samples:
            return 1.0
        return samples[len(samples) // 2]

    def _scales(self, kind: str) -> Tuple[float, float]:
        with self._lock:
            return self._seed.get(kind, (1.0, 1.0))

    # -- bucket dispatch predictions ---------------------------------------
    def decode_bucket_cost(
        self,
        words: int,
        windows: int,
        *,
        e: int,
        n: int,
        max_symlen: int = 8,
        block_words: int = 512,
        block_windows: int = 256,
    ) -> float:
        """Predicted seconds for one fused decode dispatch of a bucket of
        ``words`` packed words / ``windows`` output windows, run with the
        given megakernel block sizes (blocks shrink to the bucket when
        larger, exactly as ``decode_fused`` does, then pad the axes to
        block multiples — so oversized blocks are charged their padding
        and undersized blocks their extra grid steps)."""
        block_words = min(max(block_words, 1), max(words, 1))
        block_windows = min(max(block_windows, 1), max(windows, 1))
        wp = _round_up(max(words, 1), block_words)
        nwp = _round_up(max(windows, 1), block_windows)
        steps = _ceil_div(wp, block_words) + _ceil_div(nwp, block_windows)
        sf, sb = self._scales("decode")
        flops = sf * self.decode_flops(
            wp, nwp, e=e, n=n, max_symlen=max_symlen
        )
        nbytes = sb * self.decode_bytes(wp, nwp, e=e, n=n)
        p = self.profile
        t = (
            flops / p.peak_flops
            + nbytes / p.hbm_bps
            + steps * p.step_overhead_s
            + p.dispatch_overhead_s
        )
        return t * self.calibration("decode")

    def encode_bucket_cost(
        self,
        rows: int,
        windows_per_row: int,
        *,
        e: int,
        n: int,
        chunk_size: int = 1024,
        block_lanes: int = 128,
    ) -> float:
        """Predicted seconds for one fused encode dispatch: ``rows``
        (batch-padded) signal rows of ``windows_per_row`` windows each,
        packed as ``chunk_size``-slot chunks, ``block_lanes`` chunks per
        grid step (the lane axis pads to a block multiple, as the pack
        kernel pads it)."""
        lanes = max(rows, 1) * _ceil_div(max(windows_per_row * e, 1),
                                          chunk_size)
        block_lanes = min(max(block_lanes, 1), lanes)
        lp = _round_up(lanes, block_lanes)
        steps = _ceil_div(lp, block_lanes)
        kp = lp * chunk_size / max(windows_per_row * e, 1)
        sf, sb = self._scales("encode")
        flops = sf * self.encode_flops(kp, windows_per_row, e=e, n=n)
        nbytes = sb * self.encode_bytes(kp, windows_per_row, e=e, n=n)
        p = self.profile
        t = (
            flops / p.peak_flops
            + nbytes / p.hbm_bps
            + steps * p.step_overhead_s
            + p.dispatch_overhead_s
        )
        return t * self.calibration("encode")

    # -- per-signal costs (shard balancing) --------------------------------
    def signal_decode_cost(
        self,
        words: int,
        windows: int,
        *,
        e: int,
        n: int,
        max_symlen: int = 8,
    ) -> float:
        """One signal's share of a decode bucket — what the scheduler's
        cost-balanced shard split weighs (relative units)."""
        sf, _ = self._scales("decode")
        return sf * self.decode_flops(
            words, windows, e=e, n=n, max_symlen=max_symlen
        )

    def signal_encode_cost(
        self, windows: int, *, e: int, n: int
    ) -> float:
        """One signal's share of an encode bucket (relative units)."""
        sf, _ = self._scales("encode")
        return sf * self.encode_flops(1, windows, e=e, n=n)

    # -- policy support -----------------------------------------------------
    def edges_per_octave(
        self,
        *,
        ref_words: int = 1 << 16,
        ref_dispatches: int = 1 << 17,
        max_density: int = 4,
    ) -> int:
        """Bucket-edge density where a denser ladder stops paying.

        Going from ``d`` to ``d + 1`` edges per octave shrinks the expected
        padded fraction of every dispatch (for a geometric ladder of ratio
        ``r = 2**(1/d)`` the expected occupancy of a uniformly-sized bucket
        is ``(1 - 1/r) / ln r``) but adds roughly one jit specialization
        per octave in use.  Accept the denser ladder while the padded-word
        seconds saved over ``ref_dispatches`` dispatches of a
        ``ref_words``-word bucket exceed one ``compile_cost_s`` —
        ``ref_dispatches`` is the amortization horizon of a long-lived
        serving process, which is who pays for bucket padding.
        """
        import math

        def waste(d: int) -> float:
            r = 2.0 ** (1.0 / d)
            return 1.0 - (1.0 - 1.0 / r) / math.log(r)

        p = self.profile
        per_word_s = (
            self.decode_flops(1, 0, e=1, n=1) / p.peak_flops
            + 12.0 / p.hbm_bps
        )
        d = 1
        while d < max_density:
            saved = (
                (waste(d) - waste(d + 1))
                * ref_words
                * per_word_s
                * ref_dispatches
            )
            if saved < p.compile_cost_s:
                break
            d += 1
        return d


_DEFAULTS: Dict[str, CostModel] = {}
_DEFAULTS_LOCK = threading.Lock()


def default_cost_model(device_kind: Optional[str] = None) -> CostModel:
    """Process-wide shared model per device kind (engines constructed with
    ``cost_model=None`` resolve here, so seeding/calibrating the default
    model steers every default-constructed engine)."""
    profile = device_profile(device_kind)
    with _DEFAULTS_LOCK:
        cm = _DEFAULTS.get(profile.device_kind)
        if cm is None:
            cm = _DEFAULTS[profile.device_kind] = CostModel(profile)
        return cm
