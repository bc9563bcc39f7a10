"""Valid 3-D correlation in float64 on the host.

    y[b, o, i, j, t] = Σ_c,a,b',τ x[b, c, i+a, j+b', t+τ] · k[o, c, a, b', τ]

computed with FFTs of the signal's own size: a circular correlation of
period N equals the valid one at every valid position, where no index
wraps.  Nothing of JAX or of the program is used.
"""

from __future__ import annotations

import numpy as np
import scipy.fft as sfft

AXES = (-3, -2, -1)
WORKERS = 8


def spectrum(x: np.ndarray, shape) -> np.ndarray:
    return sfft.rfftn(np.asarray(x, np.float64), s=shape, axes=AXES,
                      workers=WORKERS)


def correlate_spectra(xs: np.ndarray, ks: np.ndarray, shape, valid):
    """xs: (C, FH, FW, FT) spectrum of one clip; ks: (O, C, ...) kernel
    spectra.  Returns the (O, *valid) valid correlation."""
    prod = np.einsum("cxyz,ocxyz->oxyz", xs, np.conj(ks))
    vol = sfft.irfftn(prod, s=shape, axes=AXES, workers=WORKERS)
    return vol[:, : valid[0], : valid[1], : valid[2]]


def correlate(x: np.ndarray, k: np.ndarray) -> np.ndarray:
    """x: (B, C, H, W, T); k: (O, C, kh, kw, kt) → (B, O, H', W', T')."""
    shape = x.shape[-3:]
    valid = tuple(n - m + 1 for n, m in zip(shape, k.shape[-3:]))
    ks = spectrum(k, shape)
    return np.stack([
        correlate_spectra(spectrum(xb, shape), ks, shape, valid) for xb in x
    ])
