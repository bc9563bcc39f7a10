"""Jit'd public wrappers for the stmul Pallas kernel.

``spectral_mac`` accepts/returns complex arrays with arbitrary trailing
frequency axes and handles the real/imag split, frequency flattening and
interpret-mode selection (interpret=True on CPU — the validation path in
this container; compiled on real TPU).  ``version`` selects the kernel
generation (2 = Karatsuba/MXU, the default; 1 = legacy broadcast-MAC).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import spectral_conv
from repro.core.spans import span
from repro.kernels.stmul import kernel as _kernel

Array = jax.Array


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile_kwargs(
    block_b: int | None, block_o: int | None, block_f: int | None
) -> dict:
    """Translate None (= kernel default) tile overrides into kwargs."""
    tiles = {}
    if block_b is not None:
        tiles["block_b"] = int(block_b)
    if block_o is not None:
        tiles["block_o"] = int(block_o)
    if block_f is not None:
        tiles["block_f"] = int(block_f)
    return tiles


@span("sthc.mac")
def spectral_mac(
    xhat: Array,
    grating: Array,
    *,
    version: int = 2,
    min_mxu_c: int | None = None,
    block_b: int | None = None,
    block_o: int | None = None,
    block_f: int | None = None,
    **tile_kwargs,
) -> Array:
    """Complex channel-contracted spectral product via the Pallas kernel.

    Args:
      xhat: (B, C, *F) complex; grating: (O, C, *F) complex.
      version: stmul kernel generation (see kernel.py).
      min_mxu_c: v2 MXU routing threshold override (None = kernel
        default) — the real-TPU tuning knob.
      block_b / block_o / block_f: tile-size overrides (None = kernel
        defaults ``BLOCK_B``/``BLOCK_O``/``BLOCK_F``); ``block_f`` must
        stay a multiple of 128 (lane width).  Surfaced as
        ``STHCConfig.stmul_block_*`` and swept in
        ``benchmarks/kernels_bench.py`` so real-TPU tile tuning needs no
        code change.

    Returns (B, O, *F) complex64.
    """
    tile_kwargs = {
        **_tile_kwargs(block_b, block_o, block_f),
        **tile_kwargs,
    }
    fshape = xhat.shape[2:]
    B, C = xhat.shape[:2]
    O = grating.shape[0]
    f = 1
    for n in fshape:
        f *= n
    xf = xhat.reshape(B, C, f)
    gf = grating.reshape(O, C, f)
    yr, yi = _kernel.spectral_mac_pallas(
        jnp.real(xf).astype(jnp.float32),
        jnp.imag(xf).astype(jnp.float32),
        jnp.real(gf).astype(jnp.float32),
        jnp.imag(gf).astype(jnp.float32),
        version=version,
        min_mxu_c=min_mxu_c,
        interpret=_use_interpret(),
        **tile_kwargs,
    )
    return (yr + 1j * yi).reshape(B, O, *fshape)


@span("sthc.mac")
def _mac_grouped_planes(
    xr: Array,
    xi: Array,
    pool_re: Array,
    pool_im: Array,
    o_start: Array,
    n_out: int,
    *,
    block_o: int | None,
    block_f: int | None,
) -> tuple[Array, Array]:
    """Pooled cross-tenant spectral MAC on lane planes, via the grouped
    Pallas kernel:

        Ŷ[b, o, bins] = Σ_c  X̂[b, c, bins] · Gpool[o_start[b] + o, c, bins]

    ``xr``/``xi`` are the (B, C, R, L) query spectra, ``pool_re`` /
    ``pool_im`` the (ΣO_pad, C, R, L) arena (float32 or bfloat16; f32
    accumulation), both as :func:`repro.core.spectral_conv.to_lane_planes`
    lays them out.  ``o_start`` holds each row's first arena row, on the
    ``block_o`` grid.  Returns the kernel's own (B, n_out, R, L) planes,
    which :func:`repro.core.spectral_conv.irfft3_lanes` reads as they
    lie."""
    return _kernel.spectral_mac_grouped_pallas(
        xr.astype(jnp.float32),
        xi.astype(jnp.float32),
        pool_re,
        pool_im,
        jnp.asarray(o_start, jnp.int32),
        n_out=int(n_out),
        interpret=_use_interpret(),
        **_tile_kwargs(None, block_o, block_f),
    )


def query_grating_pooled(
    x: Array,
    pool_re: Array,
    pool_im: Array,
    o_start: Array,
    n_out: int,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
    *,
    block_o: int | None = None,
    block_f: int | None = None,
) -> Array:
    """Pooled counterpart of :func:`query_grating_pallas`: one forward
    FFT over the stacked mixed-tenant batch, one grouped-kernel launch
    against the pooled lane-plane arena, one inverse FFT — all on split
    real / imaginary lane planes (see :func:`_mac_grouped_planes`)."""
    xr, xi = spectral_conv.rfft3_lanes(x, fft_shape)
    yr, yi = _mac_grouped_planes(
        xr,
        xi,
        pool_re,
        pool_im,
        o_start,
        n_out,
        block_o=block_o,
        block_f=block_f,
    )
    return spectral_conv.irfft3_lanes(yr, yi, fft_shape, out_shape)


@span("sthc.readout")
def topk_readout(
    vals: Array,
    gidx: Array,
    k: int,
    *,
    use_pallas: bool = True,
    block_o: int | None = None,
    block_l: int | None = None,
) -> tuple[Array, Array]:
    """Fused detection readout: reduce a flattened score axis to the K
    best (score, global position) pairs per (row, kernel).

    The serving epilogue of the streaming correlator: a window chunk's
    correlation scores never leave the reduction as a volume — only the
    tiny (B, O, K) running state does.  Selection order is total (score
    descending, index ascending), so states merge associatively via
    :func:`merge_topk` and chunked == one-shot exactly.

    Args:
      vals: (B, O, L) float32 scores (padding must carry −inf).
      gidx: (L,) int32 global flat positions
        (``kernel.TOPK_EMPTY_IDX`` marks padding).
      k: state width.
      use_pallas: route through the tiled Pallas readout kernel
        (interpret mode off-TPU); False runs the same ``topk_select``
        math as one dense jnp reduction — both are bitwise-equal.
      block_o / block_l: Pallas tile overrides (None = kernel defaults
        ``READOUT_BLOCK_O`` / ``READOUT_BLOCK_L``).

    Returns (scores, index): (B, O, k) f32 / int32.
    """
    if use_pallas:
        tiles = {}
        if block_o is not None:
            tiles["block_o"] = int(block_o)
        if block_l is not None:
            tiles["block_l"] = int(block_l)
        return _kernel.topk_readout_pallas(
            vals, gidx, k=int(k), interpret=_use_interpret(), **tiles
        )
    return _kernel.topk_select(
        vals, jnp.broadcast_to(gidx, vals.shape).astype(jnp.int32), int(k)
    )


def merge_topk(
    states: "list[tuple[Array, Array]]", k: int
) -> tuple[Array, Array]:
    """Associative merge of top-K running states.

    ``states`` is a sequence of (scores, index) pairs, each
    (..., K_i); the result is the exact top-k of the union — the merge
    the engine applies across window chunks and across stream-cursor
    segments (and the property the tests pin: any re-chunking or
    permutation of the states yields a bitwise-identical result).
    """
    s = jnp.concatenate([st[0] for st in states], axis=-1)
    i = jnp.concatenate([st[1] for st in states], axis=-1)
    return _kernel.topk_select(s, i, int(k))


def query_grating_pallas(
    x: Array,
    grating: Array,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
    *,
    version: int = 2,
    min_mxu_c: int | None = None,
    block_b: int | None = None,
    block_o: int | None = None,
    block_f: int | None = None,
) -> Array:
    """Drop-in replacement for spectral_conv.query_grating using the kernel."""
    xhat = spectral_conv.rfft3(x, fft_shape)
    yhat = spectral_mac(
        xhat,
        grating,
        version=version,
        min_mxu_c=min_mxu_c,
        block_b=block_b,
        block_o=block_o,
        block_f=block_f,
    )
    return spectral_conv.irfft3(yhat, fft_shape, out_shape)
