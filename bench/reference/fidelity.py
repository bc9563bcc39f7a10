"""The correlator's physics in plain float64 numpy, from the stated
parameters of a fidelity (the ``fidelities`` entries of a configuration).

``ideal`` is the exact correlation.  ``physical`` is the paper's stack:

- record: signed kernels split into non-negative halves K⁺ = max(K, 0)
  and K⁻ = max(−K, 0); each half, in units of the per-output-kernel
  range max|K|, quantized to the SLM's levels, tapered by the T2 decay
  of its frames, and band-limited along time on its own kt-point grid
  by the IHB envelope times the recording pulse's spectrum (divided out
  again where compensated), and scaled by range · echo efficiency.  The
  medium correlates the query with each half; the output is the
  first correlation less the second (pseudo-negative processing).
- query: the clip clamped to non-negative values, divided by its own
  peak (one dynamic range per example, over the whole stream),
  quantized to the SLM's levels; the output is multiplied back by the
  peak.
"""

from __future__ import annotations

import numpy as np


def _quantize(unit: np.ndarray, bits: int) -> np.ndarray:
    levels = float(2**bits - 1)
    return np.round(np.clip(unit, 0.0, 1.0) * levels) / levels


def _transfer(kt: int, fid: dict) -> np.ndarray:
    """Temporal transfer on the kernel's own kt-point grid."""
    f = np.fft.fftfreq(kt)
    ihb = fid["ihb"]
    if ihb["profile"] != "gaussian":
        raise ValueError(f"unsupported IHB profile {ihb['profile']!r}")
    sigma = ihb["coverage"] / (2.0 * np.sqrt(2.0 * np.log(2.0)))
    env = np.exp(-0.5 * (f / sigma) ** 2)
    env = env / env.max()
    pulse = fid["pulse"]
    sigma_f = 1.0 / (2.0 * np.pi * max(pulse["duration_frames"], 1e-6))
    p = np.exp(-0.5 * (f / sigma_f) ** 2)
    p = p / p.max()
    h = env * p
    if pulse["compensate"]:
        h = h / np.maximum(p, pulse["floor"])
    return h


def kernel_terms(kernels: np.ndarray, fid: dict) -> list:
    """(sign, (O, C, kh, kw, kt) kernels) pairs as the medium diffracts
    them: the output is the sum of sign × correlation over the pairs."""
    k = np.asarray(kernels, np.float64)
    if fid["name"] == "ideal":
        return [(1.0, k)]
    if fid["name"] != "physical":
        raise ValueError(f"unknown fidelity {fid['name']!r}")
    kt = k.shape[-1]
    rng_ = np.abs(k).max(axis=(1, 2, 3, 4), keepdims=True)
    rng_ = np.where(rng_ > 0, rng_, 1.0)
    tau = np.arange(kt)
    decay = np.exp(
        -(fid["storage_interval_s"] + (kt - 1 - tau) * fid["frame_time_s"])
        / fid["t2_s"]
    )
    h = _transfer(kt, fid)

    def written(half):
        q = _quantize(half / rng_, fid["slm_bits"]) * decay
        return np.real(np.fft.ifft(np.fft.fft(q, axis=-1) * h, axis=-1))

    echo = np.exp(-fid["storage_interval_s"] / fid["t2_s"])
    return [(1.0, written(np.maximum(k, 0.0)) * rng_ * echo),
            (-1.0, written(np.maximum(-k, 0.0)) * rng_ * echo)]


def encode(x: np.ndarray, fid: dict) -> tuple[np.ndarray, np.ndarray]:
    """(B, C, H, W, T) clips as displayed, and the per-example factor the
    output is multiplied by."""
    x = np.asarray(x, np.float64)
    ones = np.ones((x.shape[0],) + (1,) * (x.ndim - 1))
    if fid["name"] == "ideal":
        return x, ones
    x = np.maximum(x, 0.0)
    peak = x.reshape(x.shape[0], -1).max(axis=1).reshape(ones.shape)
    peak = np.where(peak > 0, peak, 1.0)
    return _quantize(x / peak, fid["slm_bits"]), peak
