"""Per-kernel Pallas validation (interpret=True on CPU) against the
pure-jnp oracles, with hypothesis shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st  # hypothesis, or deterministic fallback

from repro.core import spectral_conv as sc
from repro.kernels.conv3d import ops as conv_ops, ref as conv_ref
from repro.kernels.ssd import ops as ssd_ops, ref as ssd_ref
from repro.kernels.stmul import kernel as stmul_kernel
from repro.kernels.stmul import ops as stmul_ops, ref as stmul_ref


# -- stmul ---------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 5),
    c=st.integers(1, 4),
    o=st.integers(1, 9),
    f1=st.integers(2, 8),
    f2=st.integers(2, 10),
    f3=st.integers(2, 7),
)
def test_stmul_matches_oracle(b, c, o, f1, f2, f3):
    rng = np.random.RandomState(b * 1000 + c * 100 + o)
    sh = (f1, f2, f3)
    xh = jnp.asarray(
        (rng.randn(b, c, *sh) + 1j * rng.randn(b, c, *sh)).astype(np.complex64)
    )
    g = jnp.asarray(
        (rng.randn(o, c, *sh) + 1j * rng.randn(o, c, *sh)).astype(np.complex64)
    )
    got = stmul_ops.spectral_mac(xh, g)
    ref = stmul_ref.spectral_mac_ref(xh, g)
    np.testing.assert_allclose(got, ref, atol=1e-4 * float(jnp.max(jnp.abs(ref))) + 1e-6)


def test_stmul_tile_boundary():
    """F exactly at / off the 512-lane tile boundary."""
    rng = np.random.RandomState(0)
    for F in (511, 512, 513, 1024):
        xh = jnp.asarray(
            (rng.randn(2, 1, F) + 1j * rng.randn(2, 1, F)).astype(np.complex64)
        )
        g = jnp.asarray(
            (rng.randn(3, 1, F) + 1j * rng.randn(3, 1, F)).astype(np.complex64)
        )
        got = stmul_ops.spectral_mac(xh, g)
        ref = stmul_ref.spectral_mac_ref(xh, g)
        np.testing.assert_allclose(got, ref, atol=1e-4)


@pytest.mark.parametrize("arena_dtype", [jnp.float32, jnp.bfloat16])
def test_stmul_grouped_nine_row_block_matches_oracle(arena_dtype):
    """The grouped MAC over an arena of 9-kernel slots packed every 9
    rows, in 9-row O blocks: single-slot reads and a 27-row union span,
    offsets on the 9 grid (one shared by two rows), equal the loop
    oracle — to float32 rounding, and for a bfloat16 arena to the
    rounding of its stored planes."""
    rng = np.random.RandomState(9)
    s = (6, 7, 8)  # rfft grid: bins (6, 7, 5)
    sh = (6, 7, 5)
    pool = (rng.randn(36, 1, *sh) + 1j * rng.randn(36, 1, *sh)).astype(
        np.complex64
    )
    pr, pi = sc.to_lane_planes(
        jnp.asarray(pool.real), jnp.asarray(pool.imag), s
    )
    pr, pi = pr.astype(arena_dtype), pi.astype(arena_dtype)
    # the oracle reads the arena as stored
    fr, fi = sc.from_lane_planes(
        pr.astype(jnp.float32), pi.astype(jnp.float32), s
    )
    pool = (np.asarray(fr) + 1j * np.asarray(fi)).astype(np.complex64)
    block = stmul_kernel.grouped_block_o(9)
    assert block == 9
    for o_start, n_out in (([27, 9, 0, 9], 9), ([0, 9], 27)):
        o_start = np.asarray(o_start, np.int32)
        B = len(o_start)
        xh = (rng.randn(B, 1, *sh) + 1j * rng.randn(B, 1, *sh)).astype(
            np.complex64
        )
        ref = stmul_ref.spectral_mac_grouped_ref(
            jnp.asarray(xh), jnp.asarray(pool), o_start, n_out
        )
        ref_r, ref_i = sc.to_lane_planes(jnp.real(ref), jnp.imag(ref), s)
        xr, xi = sc.to_lane_planes(
            jnp.asarray(xh.real), jnp.asarray(xh.imag), s
        )
        got_r, got_i = stmul_kernel.spectral_mac_grouped_pallas(
            xr, xi, pr, pi, jnp.asarray(o_start), n_out=n_out,
            block_o=block, interpret=True,
        )
        assert got_r.shape == (B, n_out) + pr.shape[2:]
        tol = 1e-4 * float(jnp.max(jnp.abs(ref))) + 1e-6
        np.testing.assert_allclose(got_r, ref_r, atol=tol)
        np.testing.assert_allclose(got_i, ref_i, atol=tol)


# -- conv3d --------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(
    b=st.integers(1, 2),
    c=st.integers(1, 4),
    o=st.integers(1, 6),
    k=st.integers(1, 3),
    h=st.integers(6, 14),
    t=st.integers(4, 10),
)
def test_conv3d_matches_oracle(b, c, o, k, h, t):
    rng = np.random.RandomState(h * 10 + t)
    x = jnp.asarray(rng.randn(b, c, h, h + 2, t).astype(np.float32))
    w = jnp.asarray(rng.randn(o, c, k, k, min(k, t)).astype(np.float32))
    got = conv_ops.conv3d(x, w)
    ref = conv_ref.conv3d_ref(x, w)
    np.testing.assert_allclose(got, ref, atol=1e-3 * float(jnp.max(jnp.abs(ref))) + 1e-5)


def test_conv3d_strips_match():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, 3, 20, 16, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(4, 3, 3, 3, 3).astype(np.float32))
    ref = conv_ref.conv3d_ref(x, w)
    for strip in (4, 7, 18):
        got = conv_ops.conv3d_strips(x, w, strip_h=strip)
        np.testing.assert_allclose(got, ref, atol=1e-3)


# -- ssd -----------------------------------------------------------------


def _ssd_inputs(rng, Bb=2, L=64, H=4, P=8, G=2, N=8):
    x = jnp.asarray(rng.randn(Bb, L, H, P).astype(np.float32))
    dt = jnp.asarray((np.abs(rng.randn(Bb, L, H)) * 0.1 + 0.01).astype(np.float32))
    A = -jnp.asarray((np.abs(rng.randn(H)) + 0.5).astype(np.float32))
    B = jnp.asarray(rng.randn(Bb, L, G, N).astype(np.float32))
    C = jnp.asarray(rng.randn(Bb, L, G, N).astype(np.float32))
    return x, dt, A, B, C


@settings(max_examples=6, deadline=None)
@given(chunk=st.sampled_from([8, 16, 32, 64]), seed=st.integers(0, 100))
def test_ssd_chunk_invariance(chunk, seed):
    """Chunk size is an implementation detail — results must not move."""
    rng = np.random.RandomState(seed)
    x, dt, A, B, C = _ssd_inputs(rng)
    y_ref, S_ref = ssd_ref.ssd_scan_ref(x, dt, A, B, C)
    y, S = ssd_ops.ssd(x, dt, A, B, C, chunk=chunk, impl="jnp")
    np.testing.assert_allclose(y, y_ref, atol=2e-4 * float(jnp.max(jnp.abs(y_ref))))
    np.testing.assert_allclose(S, S_ref, atol=1e-4)


def test_ssd_pallas_matches_scan():
    rng = np.random.RandomState(3)
    x, dt, A, B, C = _ssd_inputs(rng, L=96)
    y_ref, S_ref = ssd_ref.ssd_scan_ref(x, dt, A, B, C)
    y, S = ssd_ops.ssd(x, dt, A, B, C, chunk=32, impl="pallas")
    np.testing.assert_allclose(y, y_ref, atol=2e-4 * float(jnp.max(jnp.abs(y_ref))))
    np.testing.assert_allclose(S, S_ref, atol=1e-4)


def test_ssd_pallas_ragged_length():
    """L not a multiple of chunk exercises the dt=0 padding path."""
    rng = np.random.RandomState(4)
    x, dt, A, B, C = _ssd_inputs(rng, L=77)
    y_ref, S_ref = ssd_ref.ssd_scan_ref(x, dt, A, B, C)
    y, S = ssd_ops.ssd(x, dt, A, B, C, chunk=32, impl="pallas")
    np.testing.assert_allclose(y, y_ref, atol=2e-4 * float(jnp.max(jnp.abs(y_ref))))
    np.testing.assert_allclose(S, S_ref, atol=1e-4)


def test_ssd_decode_matches_scan():
    rng = np.random.RandomState(5)
    x, dt, A, B, C = _ssd_inputs(rng, L=24)
    y_ref, S_ref = ssd_ref.ssd_scan_ref(x, dt, A, B, C)
    Bb, L, H, P = x.shape
    N = B.shape[-1]
    S = jnp.zeros((Bb, H, P, N))
    ys = []
    for t in range(L):
        S, y_t = ssd_ops.ssd_decode_step(S, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(y_t)
    np.testing.assert_allclose(
        jnp.stack(ys, 1), y_ref, atol=2e-4 * float(jnp.max(jnp.abs(y_ref)))
    )
    np.testing.assert_allclose(S, S_ref, atol=1e-4)


def test_ssd_sequence_parallel_composition():
    """Splitting L and chaining initial_state is exact — the property that
    makes sequence-parallel sharding of the SSM valid."""
    rng = np.random.RandomState(6)
    x, dt, A, B, C = _ssd_inputs(rng, L=64)
    y_ref, S_ref = ssd_ref.ssd_scan_ref(x, dt, A, B, C)
    y1, S1 = ssd_ops.ssd(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                         chunk=16, impl="jnp")
    y2, S2 = ssd_ops.ssd(x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:],
                         chunk=16, impl="jnp", initial_state=S1)
    y = jnp.concatenate([y1, y2], axis=1)
    np.testing.assert_allclose(y, y_ref, atol=2e-4 * float(jnp.max(jnp.abs(y_ref))))
    np.testing.assert_allclose(S2, S_ref, atol=1e-4)
