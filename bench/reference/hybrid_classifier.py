"""Plain reference of the paper's hybrid 3-D CNN in float64 numpy:
valid 3-D conv (9 kernels through the configured fidelity), bias, ReLU,
non-overlapping 3-D max pool, flatten, dense, ReLU, dense."""

from __future__ import annotations

import numpy as np

from bench.reference import correlation, fidelity


def max_pool(y: np.ndarray, window) -> np.ndarray:
    """Valid max pool with stride = window over the trailing 3 axes."""
    b, o = y.shape[:2]
    dims = [(n - w) // w + 1 for n, w in zip(y.shape[2:], window)]
    y = y[:, :, : dims[0] * window[0], : dims[1] * window[1],
          : dims[2] * window[2]]
    y = y.reshape(b, o, dims[0], window[0], dims[1], window[1], dims[2],
                  window[2])
    return y.max(axis=(3, 5, 7))


def logits(params: dict, clips: np.ndarray, cfg: dict, fid: dict) -> np.ndarray:
    """(B, C, H, W, T) clips → (B, classes) float64 logits."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x, gain = fidelity.encode(clips, fid)
    y = gain * sum(sign * correlation.correlate(x, k)
                   for sign, k in fidelity.kernel_terms(p["conv_w"], fid))
    y = y + p["conv_b"][None, :, None, None, None]
    y = max_pool(np.maximum(y, 0.0), cfg["pool_window"])
    y = y.reshape(y.shape[0], -1)
    y = np.maximum(y @ p["fc1_w"] + p["fc1_b"], 0.0)
    return y @ p["fc2_w"] + p["fc2_b"]
