"""Plain reference of a video search: each tenant's kernels against the
whole stream, through the tenant's fidelity, reduced to the detection
the server reports — per kernel, the peak score over space and time and
the frame it lies in."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import correlation, fidelity


@dataclasses.dataclass
class Detection:
    peak: np.ndarray  # (O,) largest correlation over (H', W', T')
    frame: np.ndarray  # (O,) the frame of the first such position
    per_frame: np.ndarray  # (O, T') largest correlation in each frame
    scale: np.ndarray  # (O,) largest |correlation|: the unit of error


class SearchReference:
    """Detections for (stream, tenant) pairs; stream spectra and tenant
    kernel spectra are computed once each and reused."""

    def __init__(self, streams, kernels, fids: list[dict]):
        self.streams = streams  # pool entry -> (1, C, H, W, T)
        self.kernels = kernels  # tenant -> (O, C, kh, kw, kt)
        self.fids = fids  # tenant -> fidelity parameters
        self._xs: dict = {}

    def _stream(self, s: int, fid: dict):
        key = (s, fid["name"])
        if key not in self._xs:
            x, gain = fidelity.encode(self.streams[s], fid)
            self._xs[key] = (correlation.spectrum(x[0], x.shape[-3:]),
                             float(gain.reshape(-1)[0]))
        return self._xs[key]

    def detections(self, pairs) -> dict:
        """{(stream, tenant): Detection} for an iterable of pairs, one
        tenant's kernel spectra at a time."""
        out = {}
        by_tenant: dict[int, list[int]] = {}
        for s, t in pairs:
            by_tenant.setdefault(t, []).append(s)
        for t, ss in sorted(by_tenant.items()):
            fid = self.fids[t]
            shape = self.streams[ss[0]].shape[-3:]
            terms = [(sign, correlation.spectrum(k, shape))
                     for sign, k in fidelity.kernel_terms(self.kernels[t], fid)]
            kshape = self.kernels[t].shape[-3:]
            valid = tuple(n - m + 1 for n, m in zip(shape, kshape))
            for s in sorted(set(ss)):
                xs, gain = self._stream(s, fid)
                vol = gain * sum(
                    sign * correlation.correlate_spectra(xs, ks, shape, valid)
                    for sign, ks in terms
                )
                flat = vol.reshape(len(vol), -1)
                out[(s, t)] = Detection(
                    peak=flat.max(-1),
                    frame=flat.argmax(-1) % vol.shape[-1],
                    per_frame=vol.max(axis=(1, 2)),
                    scale=np.abs(flat).max(-1),
                )
        return out
