"""Synthetic signals of the FPTC paper's ten datasets, vectorised.

A copy of the repository's generators (``data/signals.py``) that gives the
same samples for the same seed, bit for bit: the per-sample AR(1) loops of
the power and meteorological generators become one ``scipy.signal.lfilter``
over the same normal draws, which computes ``y[i] = x[i] + a * y[i-1]`` in
the same float64 operations.  Everything else is the original arithmetic.
The benchmark owns this copy so that its data is not part of the system
under test.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import numpy as np
from scipy.signal import lfilter

__all__ = ["DATASETS", "make_signal"]


def _ar1(rng: np.random.Generator, n: int, a: float, scale: float):
    """``ar[0] = 0; ar[i] = a * ar[i-1] + z_i * scale`` for i >= 1."""
    ar = np.zeros(n)
    if n > 1:
        ar[1:] = lfilter([1.0], [1.0, -a], rng.standard_normal(n - 1) * scale)
    return ar


def _ecg(rng, n, fs=360.0):
    t = np.arange(n) / fs
    hr = 1.1 + 0.1 * np.sin(2 * np.pi * 0.1 * t)
    phase = np.cumsum(hr) / fs
    beat_phase = phase % 1.0
    sig = np.zeros(n)
    for c, w, a in [
        (0.15, 0.025, 0.12),
        (0.235, 0.010, -0.18),
        (0.25, 0.008, 1.20),
        (0.265, 0.010, -0.25),
        (0.45, 0.045, 0.30),
    ]:
        sig += a * np.exp(-0.5 * ((beat_phase - c) / w) ** 2)
    baseline = 0.08 * np.sin(2 * np.pi * 0.25 * t + rng.uniform(0, 6))
    noise = 0.01 * rng.standard_normal(n)
    return (sig + baseline + noise).astype(np.float32)


def _eeg(rng, n, fs=250.0):
    freqs = np.fft.rfftfreq(n, 1 / fs)
    spec = rng.standard_normal(freqs.size) + 1j * rng.standard_normal(freqs.size)
    mag = np.zeros_like(freqs)
    nz = freqs > 0
    mag[nz] = 1.0 / freqs[nz]
    mag += 2.0 * np.exp(-0.5 * ((freqs - 10.0) / 1.5) ** 2)
    mag += 0.6 * np.exp(-0.5 * ((freqs - 22.0) / 3.0) ** 2)
    sig = np.fft.irfft(spec * mag, n)
    sig = sig / (np.std(sig) + 1e-9) * 20.0
    return sig.astype(np.float32)


def _seismic(rng, n, fs=500.0):
    refl = np.zeros(n)
    k = max(n // 200, 4)
    pos = rng.choice(n, size=k, replace=False)
    refl[pos] = rng.laplace(0, 1.0, size=k)
    fm = 30.0
    tw = (np.arange(-127, 128)) / fs
    ricker = (1 - 2 * (np.pi * fm * tw) ** 2) * np.exp(-((np.pi * fm * tw) ** 2))
    sig = np.convolve(refl, ricker, mode="same")
    decay = np.exp(-np.arange(n) / (n * 0.7))
    noise = 0.02 * rng.standard_normal(n)
    return ((sig * decay) + noise).astype(np.float32)


def _power(rng, n, kind="load"):
    t = np.arange(n) * 60.0
    day = 86400.0
    sig = 50.0 + 12.0 * np.sin(2 * np.pi * t / day - 1.2)
    sig += 4.0 * np.sin(4 * np.pi * t / day + 0.4)
    sig += 2.5 * np.sin(2 * np.pi * t / (7 * day))
    if kind == "solar":
        sig = np.maximum(0.0, 40.0 * np.sin(2 * np.pi * t / day - np.pi / 2))
        cloud = np.convolve(rng.standard_normal(n), np.ones(30) / 30, mode="same")
        sig *= np.clip(1.0 - 0.3 * np.abs(cloud), 0.2, 1.0)
    elif kind == "wind":
        w = np.convolve(rng.standard_normal(n), np.ones(120) / 120, mode="same")
        sig = 25.0 + 18.0 * np.tanh(2.0 * w)
    return (sig + _ar1(rng, n, 0.98, 0.15)).astype(np.float32)


def _meteo(rng, n, kind="temp"):
    t = np.arange(n) * 60.0
    day = 86400.0
    if kind == "temp":
        sig = 15.0 + 8.0 * np.sin(2 * np.pi * t / day - 2.0)
        sig += 10.0 * np.sin(2 * np.pi * t / (365 * day))
        rough = 0.05
    elif kind == "irradiance":
        sig = np.maximum(0.0, 800.0 * np.sin(2 * np.pi * t / day - np.pi / 2))
        rough = 5.0
    else:
        w = np.convolve(rng.standard_normal(n), np.ones(60) / 60, mode="same")
        sig = 6.0 + 4.0 * np.abs(w)
        rough = 0.1
    return (sig + _ar1(rng, n, 0.995, rough * 0.1)).astype(np.float32)


# name -> (domain, generator)
DATASETS: Dict[str, Tuple[str, Callable]] = {
    "mitbih": ("biomedical", _ecg),
    "ecg_arth": ("biomedical", lambda r, n: _ecg(r, n, fs=500.0)),
    "eeg_mat": ("biomedical", _eeg),
    "seismic": ("seismic", _seismic),
    "wind_power": ("power", lambda r, n: _power(r, n, kind="wind")),
    "solar_power": ("power", lambda r, n: _power(r, n, kind="solar")),
    "load_power": ("power", lambda r, n: _power(r, n, kind="load")),
    "temperature": ("meteorological", lambda r, n: _meteo(r, n, kind="temp")),
    "irradiance": ("meteorological", lambda r, n: _meteo(r, n, kind="irradiance")),
    "wind_speed": ("meteorological", lambda r, n: _meteo(r, n, kind="wind")),
}


def make_signal(name: str, num_samples: int, seed: int = 0) -> np.ndarray:
    """``num_samples`` float32 samples of the named dataset's analogue."""
    _, gen = DATASETS[name]
    return gen(np.random.default_rng(seed), num_samples)
