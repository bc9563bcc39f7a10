"""Spectral 3-D correlation: exactness vs the direct operator, in every
mode, plus overlap-save streaming equivalence (paper Fig. 1C)."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or deterministic fallback

from repro.core import fidelity as fid
from repro.core import spectral_conv as sc

TOL = 2e-4


def _rand(shape, rng, positive=False):
    x = rng.randn(*shape).astype(np.float32)
    return jnp.asarray(np.abs(x) if positive else x)


@pytest.mark.parametrize("mode", ["valid", "same", "full"])
def test_fft_matches_direct(mode, rng):
    x = _rand((2, 2, 18, 20, 12), rng)
    k = _rand((3, 2, 5, 8, 4), rng)
    a = sc.correlate3d_fft(x, k, mode=mode)
    b = sc.direct_correlate3d(x, k, mode=mode)
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, atol=TOL * float(jnp.max(jnp.abs(b))) + 1e-5)


# (input shape, FFT grid, kept outputs): zero padding on every axis, a
# crop, an even and an odd T grid (with and without a Nyquist bin)
DFT_CASES = [
    ((2, 3, 6, 8, 5), (9, 12, 8), (4, 5, 3)),
    ((1, 2, 7, 5, 9), (10, 9, 9), None),
    ((3, 4, 4, 4), (4, 4, 4), (2, 3, 4)),
    ((2, 1, 12, 10, 7), (12, 10, 7), (3, 3, 3)),
]


@pytest.mark.parametrize("shape,s,out", DFT_CASES)
def test_dft_transforms_match_numpy(shape, s, out, rng):
    """The TPU transforms (DFT matmuls on split planes) against numpy's
    float64 rfftn / irfftn."""
    x = rng.randn(*shape)
    spec = np.fft.rfftn(x, s, axes=(-3, -2, -1))
    re, im = sc.rfft3_dft(jnp.asarray(x, jnp.float32), s)
    scale = np.abs(spec).max()
    np.testing.assert_allclose(re, spec.real, atol=1e-6 * scale)
    np.testing.assert_allclose(im, spec.imag, atol=1e-6 * scale)
    y = np.fft.irfftn(spec, s, axes=(-3, -2, -1))
    if out is not None:
        y = y[..., : out[0], : out[1], : out[2]]
    got = sc.irfft3_dft(
        jnp.asarray(spec.real, jnp.float32), jnp.asarray(spec.imag, jnp.float32),
        s, out,
    )
    assert got.shape == y.shape
    np.testing.assert_allclose(got, y, atol=1e-6 * np.abs(y).max())


@pytest.mark.parametrize("shape,s,out", DFT_CASES)
def test_transform_wrappers_are_jnp_fft_off_tpu(shape, s, out, rng):
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    spec = jnp.fft.rfftn(x, s=s, axes=(-3, -2, -1))
    re, im = sc.rfft3_planes(x, s)
    assert np.array_equal(sc.rfft3(x, s), spec)
    assert np.array_equal(re, jnp.real(spec))
    assert np.array_equal(im, jnp.imag(spec))
    y = jnp.fft.irfftn(spec, s=s, axes=(-3, -2, -1))
    if out is not None:
        y = y[..., : out[0], : out[1], : out[2]]
    assert np.array_equal(sc.irfft3(spec, s, out), y)
    assert np.array_equal(sc.irfft3_planes(re, im, s, out), y)


@settings(max_examples=12, deadline=None)
@given(
    h=st.integers(6, 16),
    w=st.integers(6, 16),
    t=st.integers(4, 12),
    kh=st.integers(1, 5),
    kw=st.integers(1, 5),
    kt=st.integers(1, 4),
    c=st.integers(1, 3),
    o=st.integers(1, 3),
)
def test_fft_matches_direct_property(h, w, t, kh, kw, kt, c, o):
    rng = np.random.RandomState(h * 100 + w * 10 + t)
    x = _rand((1, c, h, w, t), rng)
    k = _rand((o, c, kh, kw, kt), rng)
    a = sc.correlate3d_fft(x, k, mode="valid")
    b = sc.direct_correlate3d(x, k, mode="valid")
    np.testing.assert_allclose(a, b, atol=TOL * float(jnp.max(jnp.abs(b))) + 1e-5)


@settings(max_examples=10, deadline=None)
@given(
    t=st.integers(8, 40),
    kt=st.integers(2, 5),
    extra=st.integers(1, 12),
)
def test_overlap_save_equals_one_shot(t, kt, extra):
    """Streaming (coherence-window) correlation ≡ one-shot correlation for
    every window size > kt−1 — the paper's segmentation is lossless.
    Runs through the engine's streaming driver (the one overlap-save
    path; spectral_conv holds only the windowing arithmetic)."""
    from repro.core.sthc import STHC, STHCConfig

    rng = np.random.RandomState(t * 7 + kt)
    x = _rand((1, 1, 10, 12, t), rng)
    k = _rand((2, 1, 3, 4, kt), rng)
    block_t = kt - 1 + extra
    ref = sc.direct_correlate3d(x, k, mode="valid")
    got = STHC(STHCConfig(fidelity=fid.ideal())).correlate_stream(k, x, block_t)
    np.testing.assert_allclose(got, ref, atol=TOL * float(jnp.max(jnp.abs(ref))) + 1e-5)


@settings(max_examples=15, deadline=None)
@given(
    t=st.integers(5, 80),
    kt=st.integers(2, 5),
    extra=st.integers(1, 12),
    chunk=st.integers(1, 6),
)
def test_stream_plan_arithmetic(t, kt, extra, chunk):
    """The pure windowing math: full coverage, whole chunks, minimal pad."""
    if t < kt:
        with pytest.raises(ValueError):
            sc.stream_plan(t, kt, kt - 1 + extra, chunk)
        return
    plan = sc.stream_plan(t, kt, kt - 1 + extra, chunk)
    assert plan.step == plan.block_t - kt + 1
    assert plan.n_valid == t - kt + 1
    # windows cover every valid output exactly once after cropping
    assert (plan.n_blocks - 1) * plan.step < plan.n_valid <= plan.n_blocks * plan.step
    assert plan.n_padded % plan.chunk == 0 and plan.n_padded >= plan.n_blocks
    assert plan.n_padded - plan.n_blocks < plan.chunk
    # padded stream is exactly long enough for the last window
    assert (plan.n_padded - 1) * plan.step + plan.block_t == t + plan.pad_t
    starts = np.asarray(sc.window_starts(plan))
    assert starts.shape == (plan.n_padded // plan.chunk, plan.chunk)
    assert starts.flatten()[-1] == (plan.n_padded - 1) * plan.step


def test_stream_plan_rejects_short_window():
    with pytest.raises(ValueError, match="block_t"):
        sc.stream_plan(20, 4, 3)


def test_grating_reuse(rng):
    """Recording once and querying many times is the weight-stationary
    dataflow — identical results for every query."""
    k = _rand((2, 1, 5, 6, 3), rng)
    sig = (16, 18, 10)
    fft_shape = sc.fft_shape_for(sig, k.shape[-3:])
    grating = sc.make_grating(k, fft_shape)
    out_shape = sc.valid_shape(sig, k.shape[-3:])
    for i in range(3):
        x = _rand((1, 1) + sig, np.random.RandomState(i))
        a = sc.query_grating(x, grating, fft_shape, out_shape)
        b = sc.direct_correlate3d(x, k, mode="valid")
        np.testing.assert_allclose(a, b, atol=TOL * float(jnp.max(jnp.abs(b))) + 1e-5)


def test_next_fast_len():
    for n in [1, 2, 3, 17, 97, 100, 129, 1000]:
        m = sc.next_fast_len(n)
        assert m >= n
        # 5-smooth check
        x = m
        for p in (2, 3, 5):
            while x % p == 0:
                x //= p
        assert x == 1, (n, m)


def test_spectral_flops_advantage():
    """The paper's large-kernel workload must favor the spectral path."""
    from repro.core.throughput import ConvWorkload

    wl = ConvWorkload()  # 30×40×8 kernels on 60×80×16 clips
    assert wl.spectral_advantage() > 5.0, wl.spectral_advantage()


# -- bounded-memory stream cursor (pure windowing arithmetic) -----------------


@settings(max_examples=16, deadline=None)
@given(
    t=st.integers(8, 90),
    kt=st.integers(2, 6),
    extra=st.integers(1, 9),
    mbw=st.integers(1, 7),
)
def test_stream_cursor_partitions_windows(t, kt, extra, mbw):
    """Cursor segments partition the plan's windows and valid outputs
    exactly: window counts sum to n_blocks, per-segment valid outputs
    tile [0, n_valid) contiguously and disjointly, and consecutive
    segments overlap by exactly kt−1 input frames (the carry-over
    tail)."""
    if t < kt:
        t = kt + t
    block_t = kt - 1 + extra
    cursor = sc.stream_cursor(t, kt, block_t, max_buffer_windows=mbw)
    plan = cursor.plan
    segs = list(cursor)
    assert sum(s.n_windows for s in segs) == plan.n_blocks
    assert segs[0].t0 == 0 and segs[0].out_t0 == 0
    out_next = 0
    for i, s in enumerate(segs):
        assert s.n_windows <= mbw
        assert s.out_t0 == out_next
        out_next += s.n_valid
        assert s.frames == s.t1 - s.t0 <= cursor.peak_buffer_frames
        if i > 0:
            prev = segs[i - 1]
            # segment input ranges overlap by the carry-over tail: the
            # next segment re-reads the kt−1 frames that straddle the
            # boundary windows (clipped at the stream tail)
            assert s.t0 == prev.t0 + prev.n_windows * plan.step
            assert prev.t1 - s.t0 == kt - 1  # exactly the carry-over
    assert out_next == plan.n_valid
    assert segs[-1].t1 <= t
    # the constant-memory bound: every segment fits the fixed buffer
    bound = (min(mbw, plan.n_blocks) - 1) * plan.step + plan.block_t
    assert cursor.peak_buffer_frames <= bound


def test_stream_cursor_single_segment_when_unbounded():
    cursor = sc.stream_cursor(40, 3, 10, max_buffer_windows=None)
    assert len(cursor) == 1
    (seg,) = cursor
    assert seg.t0 == 0 and seg.n_windows == cursor.plan.n_blocks
    assert seg.n_valid == cursor.plan.n_valid


def test_stream_cursor_rejects_bad_budget():
    plan = sc.stream_plan(40, 3, 10)
    with pytest.raises(ValueError, match="max_buffer_windows"):
        sc.StreamCursor(plan, 0)


def test_stream_cursor_segment_plans_are_consistent():
    """Each segment re-planned at its own frame count yields exactly its
    window/valid counts — the invariant the engine's chunked driver
    relies on (segment sub-plans never disagree with the cursor)."""
    cursor = sc.stream_cursor(67, 4, 12, chunk_windows=2, max_buffer_windows=3)
    for seg in cursor:
        sub = sc.stream_plan(seg.frames, 4, 12, 2)
        assert sub.n_blocks == seg.n_windows
        assert sub.n_valid == seg.n_valid
