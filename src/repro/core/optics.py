"""Optical front-end models for the STHC.

This module models the *classical-optics* elements of the correlator:

- the spatial light modulator (SLM): projects non-negative, quantized
  intensity patterns.  Trained kernels are signed, so signed values are
  handled upstream by :mod:`repro.core.pseudo_negative`; this module only
  enforces/simulates what the SLM can physically display.
- the Fourier lens: an ideal thin lens performs an exact 2-D spatial
  Fourier transform between its front and back focal planes.
- the recording pulse: a small circular aperture on the SLM whose spatial
  FT approximates a plane wave at the atomic medium, and whose short
  duration gives a temporal spectrum wider than the video's.

Everything is pure JAX and differentiable except the quantizer (which uses
a straight-through estimator so hybrid training can backprop through the
optical constraints if desired).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# SLM model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SLMConfig:
    """Physical parameters of the spatial light modulator.

    Attributes:
      bits: grey-level bit depth (Meadowlark-class SLMs are 8-12 bit).
      frame_rate_hz: full-frame update rate.  1666 fps for the commercial
        ultra-high-speed SLM cited by the paper; 125_000 fps effective when
        frames stream from a holographic memory disc (HMD).
      fill_factor: active-area fraction (applied as a global amplitude
        scale; it cancels in correlation peaks but matters for SNR models).
    """

    bits: int = 8
    frame_rate_hz: float = 1666.0
    fill_factor: float = 0.95


def quantize_unit(x: Array, bits: int) -> Array:
    """Uniformly quantize values in [0, 1] to ``2**bits`` levels.

    Uses a straight-through estimator: forward pass is quantized, gradient
    passes through unchanged.  Out-of-range inputs are clipped.
    """
    if bits <= 0:
        return x
    levels = float(2**bits - 1)
    xc = jnp.clip(x, 0.0, 1.0)
    # times the reciprocal, not divided by ``levels``: under jit XLA turns
    # a division by a constant into this product, so writing it out keeps
    # eager and jitted paths bit-identical
    q = jnp.round(xc * levels) * (1.0 / levels)
    # straight-through: value of q, gradient of xc
    return xc + jax.lax.stop_gradient(q - xc)


def quantize_signed(x: Array, bits: int) -> Array:
    """Sign-preserving quantization of values in [-1, 1].

    ``2**bits − 1`` magnitude levels per sign, zero mapped exactly to
    zero.  This is the bipolar-SLM idealization used when a fidelity
    pipeline quantizes kernels *without* pseudo-negative ± encoding
    (:class:`repro.core.fidelity.SLMQuantize` on a signed display) — a
    physical SLM cannot do this, but the ablation needs quantization's
    accuracy cost isolated from the ± split's.
    """
    if bits <= 0:
        return x
    return jnp.sign(x) * quantize_unit(jnp.abs(x), bits)


def slm_encode(frames: Array, cfg: SLMConfig) -> tuple[Array, Array]:
    """Encode (possibly signed-free, i.e. already non-negative) frames for
    the SLM.

    The SLM displays intensities in [0, 1] at finite bit depth.  Returns
    ``(encoded, scale)`` such that ``encoded * scale`` reconstructs the
    physical field amplitude presented to the optics.  ``scale`` is a
    per-example scalar (max of the frame block) so that quantization noise
    is relative, as on real hardware.

    Streaming semantics: the modulator has **one** dynamic range, so a
    long stream pushed through coherence windows (paper Fig. 1C) is
    encoded with a single *stream-global* scale — not one scale per
    window.  Quantization is pointwise, so encoding the whole stream
    once and then windowing it is exactly displaying every window at
    that shared scale; this is what makes the engine's overlap-save
    physical path (``QueryEngine.query_stream``) equal to the one-shot
    physical correlation.
    """
    frames = jnp.maximum(frames, 0.0)
    # normalize per leading example so quantization step matches hardware
    reduce_axes = tuple(range(frames.ndim - 3, frames.ndim))  # (H, W, T)
    scale = jnp.max(frames, axis=reduce_axes, keepdims=True)
    scale = jnp.where(scale > 0, scale, 1.0)
    unit = frames / scale
    encoded = quantize_unit(unit, cfg.bits) * cfg.fill_factor
    return encoded, scale / cfg.fill_factor


# ---------------------------------------------------------------------------
# Fourier lens
# ---------------------------------------------------------------------------


def lens_ft(field: Array, axes: Sequence[int] = (-2, -1)) -> Array:
    """Ideal thin-lens spatial Fourier transform (front→back focal plane).

    Orthonormal normalization keeps Parseval energy conservation — the lens
    is passive and lossless in this ideal model.
    """
    return jnp.fft.fftn(field, axes=tuple(axes), norm="ortho")


def lens_ift(field: Array, axes: Sequence[int] = (-2, -1)) -> Array:
    """Inverse lens transform (the second lens of the 4-f system)."""
    return jnp.fft.ifftn(field, axes=tuple(axes), norm="ortho")


def aperture_mask(shape_hw: tuple[int, int], radius_frac: float) -> Array:
    """Circular aperture (low-pass) mask in the Fourier plane.

    ``radius_frac`` is the passband radius as a fraction of the Nyquist
    spatial frequency.  ``radius_frac >= 1`` passes everything (the atomic
    pixel array covers the full spatial-frequency band).
    """
    h, w = shape_hw
    fy = jnp.fft.fftfreq(h)[:, None]
    fx = jnp.fft.fftfreq(w)[None, :]
    r = jnp.sqrt((fy / 0.5) ** 2 + (fx / 0.5) ** 2)
    return (r <= 2.0 * radius_frac).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Recording pulse
# ---------------------------------------------------------------------------


def recording_pulse_spatial(shape_hw: tuple[int, int], radius_px: float) -> Array:
    """The recording pulse as displayed on the SLM: a small filled circle.

    Its spatial FT (what reaches the atoms) approximates a plane wave over
    the atomic array when ``radius_px`` is small relative to the frame.
    """
    h, w = shape_hw
    yy = jnp.arange(h)[:, None] - (h - 1) / 2.0
    xx = jnp.arange(w)[None, :] - (w - 1) / 2.0
    disc = ((yy**2 + xx**2) <= radius_px**2).astype(jnp.float32)
    # normalize to unit energy so pulse amplitude is shape-independent
    return disc / jnp.sqrt(jnp.maximum(jnp.sum(disc**2), 1.0))


def recording_pulse_spectrum(
    shape_hw: tuple[int, int], radius_px: float = 1.5
) -> Array:
    """Spatial spectrum of the recording pulse at the atomic plane.

    For the *ideal* mode this is treated as exactly flat (unit amplitude);
    this function returns the *physical* spectrum — an Airy-like pattern —
    used by the physical-fidelity mode to model residual non-uniformity.
    The returned spectrum is normalized to unit peak so that dividing by it
    (deconvolution) is well-conditioned near DC.
    """
    pulse = recording_pulse_spatial(shape_hw, radius_px)
    spec = jnp.abs(jnp.fft.fft2(pulse))
    return spec / jnp.maximum(jnp.max(spec), 1e-12)


def temporal_pulse_spectrum(n_t: int, duration_frames: float = 0.25) -> Array:
    """Temporal spectrum of the (short) recording pulse.

    A pulse much shorter than one frame has a temporal spectrum flat over
    the video band.  Modeled as a Gaussian with ``sigma_t = duration`` in
    frame units; normalized to unit peak.
    """
    f = jnp.fft.fftfreq(n_t)  # cycles / frame
    sigma_f = 1.0 / (2.0 * jnp.pi * max(duration_frames, 1e-6))
    spec = jnp.exp(-0.5 * (f / sigma_f) ** 2)
    return spec / jnp.maximum(jnp.max(spec), 1e-12)
