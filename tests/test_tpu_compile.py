"""Compile the served path's Pallas kernels for a TPU v5e chip that is
described, not attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
tiles off the (8, 128) grid, VMEM over budget, block specs it cannot
lower.  These tests hand the TPU compiler the kernels at the paper's
served geometry — 60×80 frames against 30×40×8 kernels in 64-frame
coherence windows, four windows per chunk — and assert each compiled
program holds a Mosaic kernel (``tpu_custom_call``).  Nothing runs: a
pass here says the kernels compile, not that they are right or fast.

The topology is described inside a module fixture (never at import):
only one process may hold the TPU library, so the first test of this
file loads it in the worker that runs the file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import spectral_conv
from repro.kernels.stmul import kernel as stmul_kernel
from repro.kernels.stmul import ops as stmul_ops

# the served paper geometry (configs/sthc_kth.py, VideoSearchConfig)
FRAME_HW = (60, 80)
KER = (30, 40, 8)
WINDOW_FRAMES = 64
CHUNK_WINDOWS = 4
STREAM_FRAMES = 256
N_KERNELS = 9  # paper's optical channels = per-tenant O
ROWS = 8  # physical stream rows in one pooled dispatch
TENANTS = 4  # tenants sharing one arena (one encode group of the smoke)


def _geometry():
    fft = spectral_conv.fft_shape_for(FRAME_HW + (WINDOW_FRAMES,), KER)
    f_bins = fft[0] * fft[1] * (fft[2] // 2 + 1)  # rfft over the last axis
    plan = spectral_conv.stream_plan(
        STREAM_FRAMES, KER[2], WINDOW_FRAMES, CHUNK_WINDOWS
    )
    oh, ow = (n - k + 1 for n, k in zip(FRAME_HW, KER[:2]))
    n_scores = plan.chunk * oh * ow * plan.step  # one chunk's readout axis
    slot = -(-N_KERNELS // stmul_kernel.BLOCK_O) * stmul_kernel.BLOCK_O
    return f_bins, n_scores, TENANTS * slot


F_BINS, N_SCORES, ARENA_ROWS = _geometry()


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paper_geometry_matches_served_shapes():
    assert (F_BINS, N_SCORES, ARENA_ROWS) == (399_600, 289_788, 64)


@pytest.mark.parametrize("arena_dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_stmul_compiles(one_chip, arena_dtype):
    x = _spec((ROWS, 1, F_BINS), jnp.float32, one_chip)
    g = _spec((ARENA_ROWS, 1, F_BINS), arena_dtype, one_chip)
    o = _spec((ROWS,), jnp.int32, one_chip)
    _assert_mosaic(
        lambda xr, xi, gr, gi, off: stmul_kernel.spectral_mac_grouped_pallas(
            xr, xi, gr, gi, off, n_out=N_KERNELS
        ),
        x, x, g, g, o,
    )


def test_grouped_stmul_compiles_under_vmap(one_chip):
    """The engine maps the grouped launch over a chunk's windows."""
    x = _spec((CHUNK_WINDOWS, ROWS, 1, F_BINS), jnp.float32, one_chip)
    g = _spec((ARENA_ROWS, 1, F_BINS), jnp.float32, one_chip)
    o = _spec((ROWS,), jnp.int32, one_chip)

    def chunk(xr, xi, gr, gi, off):
        return jax.vmap(
            lambda a, b: stmul_kernel.spectral_mac_grouped_pallas(
                a, b, gr, gi, off, n_out=N_KERNELS
            )
        )(xr, xi)

    _assert_mosaic(chunk, x, x, g, g, o)


def test_pooled_window_query_compiles(one_chip, monkeypatch):
    """One window of the served pooled query as the chip runs it: the
    DFT-matmul transforms around the grouped MAC.  One stream row: the
    compile time of the transforms grows with the batch, their widths
    are what the chip could refuse."""
    monkeypatch.setattr(spectral_conv, "_use_dft", lambda: True)
    monkeypatch.setattr(stmul_ops, "_use_interpret", lambda: False)
    fft = spectral_conv.fft_shape_for(FRAME_HW + (WINDOW_FRAMES,), KER)
    out = spectral_conv.valid_shape(FRAME_HW + (WINDOW_FRAMES,), KER)
    x = _spec((1, 1) + FRAME_HW + (WINDOW_FRAMES,), jnp.float32, one_chip)
    g = _spec((ARENA_ROWS, 1, fft[0], fft[1], fft[2] // 2 + 1), jnp.float32,
              one_chip)
    o = _spec((1,), jnp.int32, one_chip)
    _assert_mosaic(
        lambda x, gr, gi, off: stmul_ops.query_grating_pooled(
            x, gr, gi, off, N_KERNELS, fft, out
        ),
        x, g, g, o,
    )


def test_stmul_v2_compiles(one_chip):
    x = _spec((ROWS, 1, F_BINS), jnp.float32, one_chip)
    g = _spec((N_KERNELS, 1, F_BINS), jnp.float32, one_chip)
    _assert_mosaic(
        lambda xr, xi, gr, gi: stmul_kernel.spectral_mac_pallas(
            xr, xi, gr, gi, version=2
        ),
        x, x, g, g,
    )


@pytest.mark.parametrize("k", [1, 3])
def test_topk_readout_compiles(one_chip, k):
    vals = _spec((ROWS, N_KERNELS, N_SCORES), jnp.float32, one_chip)
    gidx = _spec((N_SCORES,), jnp.int32, one_chip)
    _assert_mosaic(
        lambda v, i: stmul_kernel.topk_readout_pallas(v, i, k=k), vals, gidx
    )
