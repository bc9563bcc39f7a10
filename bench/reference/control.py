"""The control: the reference's mathematics on the device at the next
precision below the configuration's float32 at HIGHEST — three
bfloat16 passes (``high``): each float32 operand of a matrix product
split into a bfloat16 head and a bfloat16 tail, and head·head +
head·tail + tail·head summed in float32.  Written out, so it runs the
same on any backend.

The correlation is the reference's own: discrete Fourier transforms of
the signal's size along each of the three axes (here as matrix
products), the spectra multiplied, and the inverse transform cropped to
the valid positions.  Elementwise arithmetic stays float32.

The correctness comparison has to fail it; ``bench/control.py`` reads
it on the chip and ``bench/tests`` keeps it at a small size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference import fidelity


def _split(a):
    a = jnp.asarray(a, jnp.float32)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _three_pass(op, a, b):
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _dot(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _dft(re, im, axis: int, inverse: bool):
    """Complex DFT along ``axis`` as three-pass real matrix products."""
    n = re.shape[axis]
    ang = 2.0 * np.pi * np.outer(np.arange(n), np.arange(n)) / n
    c = jnp.asarray(np.cos(ang), jnp.float32)
    s = jnp.asarray((1.0 if inverse else -1.0) * np.sin(ang), jnp.float32)
    re, im = jnp.moveaxis(re, axis, -1), jnp.moveaxis(im, axis, -1)
    out_re = _three_pass(_dot, re, c) - _three_pass(_dot, im, s)
    out_im = _three_pass(_dot, re, s) + _three_pass(_dot, im, c)
    return jnp.moveaxis(out_re, -1, axis), jnp.moveaxis(out_im, -1, axis)


def _spectrum(x):
    re, im = x, jnp.zeros_like(x)
    for axis in (-3, -2, -1):
        re, im = _dft(re, im, axis, inverse=False)
    return re, im


@functools.partial(jax.jit, static_argnames="valid")
def _correlate(x, k, valid):
    xr, xi = _spectrum(x)  # (B, C, H, W, T)
    kr, ki = _spectrum(k)  # (O, C, H, W, T), zero-padded to the signal
    # X · conj(K), summed over channels, elementwise in float32
    xr, xi, kr, ki = xr[:, None], xi[:, None], kr[None], ki[None]
    pr = jnp.sum(xr * kr + xi * ki, axis=2)
    pi = jnp.sum(xi * kr - xr * ki, axis=2)
    for axis in (-3, -2, -1):
        pr, pi = _dft(pr, pi, axis, inverse=True)
    n = x.shape[-3] * x.shape[-2] * x.shape[-1]
    return pr[..., : valid[0], : valid[1], : valid[2]] / n


def correlate_high(x, k):
    """Valid correlation (B, C, H, W, T) ⋆ (O, C, kh, kw, kt) through
    transforms of the signal's own size, at three passes."""
    x = np.asarray(x, np.float32)
    k = np.asarray(k, np.float32)
    valid = tuple(n - m + 1 for n, m in zip(x.shape[-3:], k.shape[-3:]))
    pad = [(0, 0), (0, 0)] + [(0, n - m) for n, m in zip(x.shape[-3:],
                                                          k.shape[-3:])]
    return _correlate(x, np.pad(k, pad), valid)


def search_detections(stream, kernels, fid):
    """(peak, frame) per kernel of one request, as the server reports."""
    x, gain = fidelity.encode(stream, fid)
    vol = np.asarray(sum(sign * correlate_high(x, k)
                         for sign, k in fidelity.kernel_terms(kernels, fid)))
    vol = vol[0] * np.float32(gain.reshape(-1)[0])
    flat = vol.reshape(len(vol), -1)
    return flat.max(-1), flat.argmax(-1) % vol.shape[-1]


def classifier_logits(params: dict, clips, cfg: dict, fid: dict):
    """(B, classes) logits of the hybrid CNN at three-pass precision."""
    x, gain = fidelity.encode(clips, fid)
    y = sum(sign * correlate_high(x, k) for sign, k in
            fidelity.kernel_terms(np.asarray(params["conv_w"]), fid))
    y = y * jnp.asarray(gain, jnp.float32)
    y = y + jnp.asarray(params["conv_b"])[None, :, None, None, None]
    y = jnp.maximum(y, 0.0)
    win = (1, 1) + tuple(cfg["pool_window"])
    y = lax.reduce_window(y, -jnp.inf, lax.max, win, win, "VALID")
    y = y.reshape(y.shape[0], -1)
    y = _three_pass(_dot, y, params["fc1_w"]) + jnp.asarray(params["fc1_b"])[None]
    y = jnp.maximum(y, 0.0)
    return np.asarray(
        _three_pass(_dot, y, params["fc2_w"]) + jnp.asarray(params["fc2_b"])[None]
    )
