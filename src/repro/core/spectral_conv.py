"""Spectral (Fourier-domain) 3-D correlation — the TPU-native STHC math.

The optical system computes correlation as a pointwise product in the 3-D
Fourier domain: spatial FT by a lens, temporal FT by the atomic coherence
grating + photon echo.  On TPU the faithful analogue is FFT-based
correlation with a **precomputed kernel spectrum ("grating")** that is
stored once and reused across queries (weight-stationary dataflow):

    record:   G[o, c, f]  = conj( FFT3(K[o, c]) )               (once)
    query:    Ŷ[b, o, f]  = Σ_c  FFT3(X[b, c])[f] · G[o, c, f]   (per clip)
    readout:  Y[b, o]     = IFFT3(Ŷ[b, o])[valid region]

For the paper's kernels (30×40×8 = 9 600 taps) spectral correlation is
~40× cheaper in FLOPs than direct correlation — the same asymmetry that
makes the optical implementation attractive.

Conventions
-----------
* Signals are real; we use rfftn over the last three axes (H, W, T).
* "Correlation" is the CNN forward operator  Y[i] = Σ_m K[m] X[i+m]
  (no kernel flip) — identical to what `lax.conv_general_dilated` computes.
* With FFT length L ≥ N the circular correlation's first  N−K+1  samples
  are exactly the *valid* linear correlation, so valid mode needs no roll.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array

_FFT_AXES = (-3, -2, -1)


def next_fast_len(n: int) -> int:
    """Smallest 5-smooth (2^a 3^b 5^c) integer ≥ n — fast FFT sizes."""
    if n <= 1:
        return 1
    best = 1 << (n - 1).bit_length()  # fallback: next power of two
    p5 = 1
    while p5 < best:
        p35 = p5
        while p35 < best:
            # smallest power of two lifting p35 to >= n
            x = p35
            while x < n:
                x *= 2
            if x < best:
                best = x
            p35 *= 3
        p5 *= 5
    return best


def fft_shape_for(
    sig_shape: Sequence[int], ker_shape: Sequence[int], fast: bool = True
) -> tuple[int, ...]:
    """FFT grid for a linear (non-circular) correlation: ≥ N + K − 1."""
    full = [int(n) + int(k) - 1 for n, k in zip(sig_shape, ker_shape)]
    if fast:
        full = [next_fast_len(n) for n in full]
    return tuple(full)


def valid_shape(sig_shape: Sequence[int], ker_shape: Sequence[int]) -> tuple[int, ...]:
    return tuple(int(n) - int(k) + 1 for n, k in zip(sig_shape, ker_shape))


# ---------------------------------------------------------------------------
# 3-D real transforms
# ---------------------------------------------------------------------------
# Every correlation path transforms through these.  Off-TPU they are
# jnp.fft's rfftn / irfftn.  On TPU they run as DFT matmuls on split
# real / imaginary float32 planes: with libtpu 0.0.34 (jax 0.9.0) XLA's
# inverse 3-D FFT returns wrong values on a v5e chip — ``irfftn`` was off
# by about 40 % of the signal's peak over the served (…, 90, 120, 37)
# grids, on some shapes only in some runs — and so did an inverse DFT
# written as complex64 einsums.  Real float32 matmuls at HIGHEST
# precision are exact there.  The planes form also lets the Pallas MAC,
# which takes split planes, skip the complex dtype altogether.

Planes = tuple[Array, Array]


def _use_dft() -> bool:
    return jax.default_backend() == "tpu"


def _crop(y: Array, out: Sequence[int] | None) -> Array:
    if out is None:
        return y
    return y[..., : out[0], : out[1], : out[2]]


def rfft3_planes(x: Array, s: Sequence[int]) -> Planes:
    """``rfftn(x, s=s)`` over the trailing (H, W, T) axes as (real,
    imaginary) float32 planes of shape (..., FH, FW, FT//2+1)."""
    if _use_dft():
        return rfft3_dft(x, s)
    spec = jnp.fft.rfftn(x, s=s, axes=_FFT_AXES)
    return jnp.real(spec), jnp.imag(spec)


def irfft3_planes(
    re: Array, im: Array, s: Sequence[int], out: Sequence[int] | None = None
) -> Array:
    """``irfftn(re + i·im, s=s)`` over the trailing axes, cropped to the
    leading ``out`` samples of each axis (the whole grid when None)."""
    if _use_dft():
        return irfft3_dft(re, im, s, out)
    return _crop(jnp.fft.irfftn(lax.complex(re, im), s=s, axes=_FFT_AXES), out)


def rfft3(x: Array, s: Sequence[int]) -> Array:
    """Complex ``rfftn(x, s=s)`` over the trailing (H, W, T) axes."""
    if _use_dft():
        return lax.complex(*rfft3_dft(x, s))
    return jnp.fft.rfftn(x, s=s, axes=_FFT_AXES)


def irfft3(y: Array, s: Sequence[int], out: Sequence[int] | None = None) -> Array:
    """``irfftn(y, s=s)`` over the trailing axes, cropped to ``out``."""
    if _use_dft():
        return irfft3_dft(jnp.real(y), jnp.imag(y), s, out)
    return _crop(jnp.fft.irfftn(y, s=s, axes=_FFT_AXES), out)


def _dft_cos_sin(n_in: int, n_out: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """cos / sin of θ[a, k] = 2π·a·k / n for a < n_in, k < n_out."""
    theta = 2 * np.pi * np.outer(np.arange(n_in), np.arange(n_out)) / n
    return np.cos(theta), np.sin(theta)


def _cmatmul(re: Array, im: Array, spec: str, c: np.ndarray, s: np.ndarray) -> Planes:
    """(re + i·im) contracted by ``spec`` with the matrix c + i·s."""
    hi = lax.Precision.HIGHEST
    c = c.astype(np.float32)
    s = s.astype(np.float32)

    def mm(a, m):
        return jnp.einsum(spec, a, m, precision=hi)

    return mm(re, c) - mm(im, s), mm(re, s) + mm(im, c)


_H_SPEC = "...hwk,ha->...awk"
_W_SPEC = "...wk,wa->...ak"


def rfft3_dft(x: Array, s: Sequence[int]) -> Planes:
    """Forward ``rfftn`` as float32 matmuls against DFT matrices: the
    real transform along T, then complex transforms along W and H.  Zero
    padding to ``s`` is folded into the matrices (only the signal's own
    samples are multiplied); longer inputs are cropped, as ``rfftn``
    crops them."""
    h, w, n = (int(d) for d in s)
    x = x[..., :h, :w, :n].astype(jnp.float32)
    hin, win, tin = x.shape[-3:]
    cos, sin = _dft_cos_sin(tin, n // 2 + 1, n)
    hi = lax.Precision.HIGHEST
    re = jnp.einsum("...t,tk->...k", x, cos.astype(np.float32), precision=hi)
    im = -jnp.einsum("...t,tk->...k", x, sin.astype(np.float32), precision=hi)
    c, s_ = _dft_cos_sin(win, w, w)
    re, im = _cmatmul(re, im, _W_SPEC, c, -s_)
    c, s_ = _dft_cos_sin(hin, h, h)
    return _cmatmul(re, im, _H_SPEC, c, -s_)


def irfft3_dft(
    re: Array, im: Array, s: Sequence[int], out: Sequence[int] | None = None
) -> Array:
    """Inverse ``rfftn`` as float32 matmuls against DFT matrices, only
    for the ``out`` samples kept: complex inverse transforms along H and
    W, then the real inverse along T, whose bins other than DC and
    Nyquist stand for their conjugate twins too (weight 2) and give only
    their real part Re(Z·e^{iθ}) = Re Z·cos θ − Im Z·sin θ."""
    h, w, n = (int(d) for d in s)
    oh, ow, on = (h, w, n) if out is None else (int(d) for d in out)
    c, s_ = _dft_cos_sin(h, oh, h)
    re, im = _cmatmul(re, im, _H_SPEC, c / h, s_ / h)
    c, s_ = _dft_cos_sin(w, ow, w)
    re, im = _cmatmul(re, im, _W_SPEC, c / w, s_ / w)
    k = np.arange(n // 2 + 1)
    weight = np.where((k == 0) | (2 * k == n), 1.0, 2.0)[:, None] / n
    cos, sin = _dft_cos_sin(n // 2 + 1, on, n)
    hi = lax.Precision.HIGHEST
    return jnp.einsum(
        "...k,kt->...t", re, (weight * cos).astype(np.float32), precision=hi
    ) - jnp.einsum(
        "...k,kt->...t", im, (weight * sin).astype(np.float32), precision=hi
    )


# ---------------------------------------------------------------------------
# Grating (record) and query (diffraction + echo readout)
# ---------------------------------------------------------------------------


def make_grating(
    kernels: Array,
    fft_shape: tuple[int, int, int],
    spatial_transfer: Array | None = None,
) -> Array:
    """Record kernels into a frequency-domain grating.

    Temporal medium envelopes (IHB/pulse, physical mode) are *not*
    applied here: the engine applies them on the kernel's own kt-point
    grid at record time so the grating is query-geometry-independent —
    an envelope sampled on this query FFT grid would make the recorded
    medium depend on the clip being searched.

    Args:
      kernels: (O, C, kh, kw, kt) real kernel stack.
      fft_shape: 3-D FFT grid (from :func:`fft_shape_for`).
      spatial_transfer: optional lens/aperture transfer over (f_y, f_x),
        shape fft_shape[:2].

    Returns:
      Complex grating (O, C, FH, FW, FT//2+1) — ``conj(rfftn(K))``.
      This is the tensor held stationary in HBM (the analogue of the
      stored atomic coherence).
    """
    grating = jnp.conj(rfft3(kernels, fft_shape))
    if spatial_transfer is not None:
        grating = grating * spatial_transfer[..., :, :, None]
    return grating


def query_grating(
    x: Array,
    grating: Array,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
    *,
    precision: lax.Precision | str = "highest",
) -> Array:
    """Diffract a query video off the stored grating (the STHC hot path).

    Args:
      x: (B, C, H, W, T) real query clips.
      grating: (O, C, FH, FW, FTr) complex grating from make_grating.
      fft_shape: the 3-D FFT grid used at record time.
      out_shape: cropped (valid) output spatial-temporal shape.

    Returns:
      (B, O, *out_shape) real correlation feature maps.
    """
    xhat = rfft3(x, fft_shape)  # (B,C,FH,FW,FTr)
    # Channel-contracted spectral product — the 'diffraction' step.
    yhat = jnp.einsum("bcxyz,ocxyz->boxyz", xhat, grating, precision=precision)
    return irfft3(yhat, fft_shape, out_shape)


# ---------------------------------------------------------------------------
# One-shot correlation APIs
# ---------------------------------------------------------------------------


def correlate3d_fft(
    x: Array,
    kernels: Array,
    mode: str = "valid",
    spatial_transfer: Array | None = None,
) -> Array:
    """FFT-based multi-channel 3-D correlation.

    Args:
      x: (B, C, H, W, T); kernels: (O, C, kh, kw, kt).
      mode: 'valid' | 'same' | 'full'.

    Returns (B, O, H', W', T') with H' per mode.
    """
    sig = x.shape[-3:]
    ker = kernels.shape[-3:]
    fft_shape = fft_shape_for(sig, ker)
    grating = make_grating(kernels, fft_shape, spatial_transfer)
    full = tuple(n + k - 1 for n, k in zip(sig, ker))
    if mode == "valid":
        out = valid_shape(sig, ker)
        return query_grating(x, grating, fft_shape, out)
    # full / same need the negative lags, which wrap circularly: roll by K-1.
    xhat = rfft3(x, fft_shape)
    yhat = jnp.einsum("bcxyz,ocxyz->boxyz", xhat, grating, precision="highest")
    y = irfft3(yhat, fft_shape)
    shifts = tuple(k - 1 for k in ker)
    y = jnp.roll(y, shifts, axis=_FFT_AXES)
    y = y[..., : full[0], : full[1], : full[2]]
    if mode == "full":
        return y
    if mode == "same":
        # XLA SAME pads (k-1)//2 low — the same crop start is k//2 in full-
        # correlation indexing (matters for even kernel dims).
        starts = tuple(k // 2 for k in ker)
        return y[
            ...,
            starts[0] : starts[0] + sig[0],
            starts[1] : starts[1] + sig[1],
            starts[2] : starts[2] + sig[2],
        ]
    raise ValueError(f"unknown mode {mode!r}")


def direct_correlate3d(x: Array, kernels: Array, mode: str = "valid") -> Array:
    """Direct (digital-baseline) 3-D correlation via lax.conv.

    XLA's conv is cross-correlation (no kernel flip) — the same operator
    as the optical correlator.  x: (B, C, H, W, T); kernels (O, C, ...).
    """
    if mode == "valid":
        padding = "VALID"
    elif mode == "same":
        padding = "SAME"
    elif mode == "full":
        padding = [(k - 1, k - 1) for k in kernels.shape[-3:]]
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return lax.conv_general_dilated(
        x,
        kernels,
        window_strides=(1, 1, 1),
        padding=padding,
        dimension_numbers=("NCHWD", "OIHWD", "NCHWD"),
        precision=lax.Precision.HIGHEST,
    )


# ---------------------------------------------------------------------------
# Overlap-save windowing math (paper Fig. 1C as arithmetic)
# ---------------------------------------------------------------------------
# The paper segments a T3-long database into coherence windows of T2 frames
# overlapping by the query length T1 (Fig. 1C).  That scheme *is* overlap-save
# block convolution: each block of ``block_t`` frames overlaps the previous by
# ``kt − 1`` frames and contributes ``block_t − kt + 1`` valid outputs.
#
# The driver that actually slides windows over a stream lives in
# :meth:`repro.core.engine.QueryEngine.query_stream` — the one streaming path
# shared by ``STHC.correlate_stream``, hybrid long-clip inference and the
# video-search server.  This module keeps only the pure windowing arithmetic
# (plan + reassembly), so the geometry is testable in isolation and the
# engine owns the dataflow (and its physical-encoding semantics).


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Window arithmetic of one overlap-save pass.

    All fields are Python ints, so a plan is hashable and can be a static
    argument of a jitted driver.

    Attributes:
      block_t: frames per coherence window (T2).
      step: valid outputs contributed per window (= block_t − kt + 1).
      n_valid: total valid outputs (= T − kt + 1).
      n_blocks: windows actually required to cover the stream.
      chunk: windows correlated per step as one vmap'd batch.
      n_padded: n_blocks rounded up to whole chunks.
      pad_t: zero frames appended to the stream tail so every window
        (including chunk-fill windows) is full length; the surplus
        outputs are cropped by :func:`stitch_windows`.
    """

    block_t: int
    step: int
    n_valid: int
    n_blocks: int
    chunk: int
    n_padded: int
    pad_t: int


def stream_plan(
    T: int, kt: int, block_t: int, chunk_windows: int | None = None
) -> StreamPlan:
    """Plan an overlap-save pass over a T-frame stream (pure arithmetic)."""
    T, kt, block_t = int(T), int(kt), int(block_t)
    if block_t <= kt - 1:
        raise ValueError(f"block_t ({block_t}) must exceed kt-1 ({kt - 1})")
    if T < kt:
        raise ValueError(f"stream length ({T}) is shorter than kt ({kt})")
    step = block_t - (kt - 1)
    n_valid = T - kt + 1
    n_blocks = -(-n_valid // step)  # ceil
    chunk = max(1, min(int(chunk_windows or 1), n_blocks))
    n_padded = -(-n_blocks // chunk) * chunk  # round up to whole chunks
    pad_t = max((n_padded - 1) * step + block_t - T, 0)
    return StreamPlan(block_t, step, n_valid, n_blocks, chunk, n_padded, pad_t)


@dataclasses.dataclass(frozen=True)
class StreamSegment:
    """One bounded-buffer slice of an overlap-save pass (pure ints).

    A segment is a contiguous run of coherence windows served from one
    fixed-size device buffer.  Consecutive segments overlap by
    ``kt − 1`` input frames (the carry-over tail): segment boundaries
    fall on window-start positions, so every window is computed from
    exactly the frames a one-shot pass would read — chunked streaming is
    equal to one-shot correlation, not an approximation.

    Attributes:
      index: segment position in the cursor order.
      t0 / t1: input frame range ``[t0, t1)`` this segment consumes
        (``t1`` is clipped to the stream length for the tail segment).
      frames: ``t1 − t0`` — the device buffer this segment needs.
      n_windows: coherence windows this segment serves.
      out_t0: first valid-output index the segment produces; segment
        outputs are contiguous and disjoint, so concatenating them in
        cursor order reassembles the one-shot valid correlation.
      n_valid: valid outputs the segment produces.
    """

    index: int
    t0: int
    t1: int
    frames: int
    n_windows: int
    out_t0: int
    n_valid: int


class StreamCursor:
    """Bounded-memory iteration plan over one overlap-save pass.

    Splits a :class:`StreamPlan` of ``n_blocks`` windows into segments
    of at most ``max_buffer_windows`` windows each, so a stream whose T
    exceeds one device buffer is served at **constant peak memory**:
    every segment needs at most ``(max_buffer_windows − 1) · step +
    block_t`` input frames on device, regardless of T.  All fields are
    Python ints — segments are static arguments of the jitted driver,
    and every non-tail segment shares one trace (identical geometry).
    """

    def __init__(self, plan: StreamPlan, max_buffer_windows: int):
        if max_buffer_windows < 1:
            raise ValueError(
                f"max_buffer_windows must be >= 1, got {max_buffer_windows}"
            )
        self.plan = plan
        self.max_buffer_windows = int(max_buffer_windows)
        kt = plan.block_t - plan.step + 1
        T = plan.n_valid + kt - 1
        segments: list[StreamSegment] = []
        done = 0
        while done < plan.n_blocks:
            n = min(self.max_buffer_windows, plan.n_blocks - done)
            t0 = done * plan.step
            t1 = min(t0 + (n - 1) * plan.step + plan.block_t, T)
            out_t0 = done * plan.step
            n_valid = min(t1 - t0 - kt + 1, plan.n_valid - out_t0)
            segments.append(
                StreamSegment(
                    index=len(segments),
                    t0=t0,
                    t1=t1,
                    frames=t1 - t0,
                    n_windows=n,
                    out_t0=out_t0,
                    n_valid=n_valid,
                )
            )
            done += n
        self.segments = tuple(segments)

    @property
    def peak_buffer_frames(self) -> int:
        """Largest per-segment input buffer — the constant-memory bound."""
        return max(s.frames for s in self.segments)

    def __iter__(self):
        return iter(self.segments)

    def __len__(self) -> int:
        return len(self.segments)


def stream_cursor(
    T: int,
    kt: int,
    block_t: int,
    chunk_windows: int | None = None,
    max_buffer_windows: int | None = None,
) -> StreamCursor:
    """Cursor over a freshly-planned overlap-save pass (pure arithmetic).

    ``max_buffer_windows=None`` means one segment spanning the whole
    stream (the unbounded one-shot driver)."""
    plan = stream_plan(T, kt, block_t, chunk_windows)
    if max_buffer_windows is None:
        max_buffer_windows = plan.n_blocks
    return StreamCursor(plan, max_buffer_windows)


def window_starts(plan: StreamPlan) -> Array:
    """First-frame indices of every window, grouped (n_outer, chunk)."""
    return (jnp.arange(plan.n_padded) * plan.step).reshape(-1, plan.chunk)


def stitch_windows(blocks: Array, plan: StreamPlan) -> Array:
    """Reassemble per-window valid outputs into the stream's time axis.

    Args:
      blocks: (n_outer, chunk, B, O, H', W', step) window outputs, in
        :func:`window_starts` order.

    Returns (B, O, H', W', n_valid) — the one-shot valid correlation.
    """
    blocks = blocks.reshape((plan.n_padded,) + blocks.shape[2:])
    blocks = jnp.moveaxis(blocks, 0, -2)  # (B, O, H', W', n_padded, step)
    y = blocks.reshape(blocks.shape[:-2] + (plan.n_padded * plan.step,))
    return y[..., : plan.n_valid]
