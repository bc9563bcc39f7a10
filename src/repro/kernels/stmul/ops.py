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


def spectral_mac_grouped(
    xhat: Array,
    pool_re: Array,
    pool_im: Array,
    o_start: Array,
    n_out: int,
    *,
    min_mxu_c: int | None = None,
    block_o: int | None = None,
    block_f: int | None = None,
) -> Array:
    """Pooled cross-tenant spectral MAC via the grouped Pallas kernel.

        Ŷ[b, o, f] = Σ_c  X̂[b, c, f] · Gpool[o_start[b] + o, c, f]

    Args:
      xhat: (B, C, *F) complex query spectra (the stacked mixed-tenant
        batch).
      pool_re / pool_im: (ΣO_pad, C, *F) split real/imag planes of the
        pooled grating arena — float32 or bfloat16 (half-precision
        grating storage; the kernel up-casts tiles, f32 accumulation).
      o_start: (B,) int32 per-row first-row offsets into the arena, on
        the ``block_o`` grid.
      n_out: O rows produced per query row.

    Returns (B, n_out, *F) complex64.
    """
    yr, yi = _mac_grouped_planes(
        jnp.real(xhat), jnp.imag(xhat), pool_re, pool_im, o_start, n_out,
        min_mxu_c=min_mxu_c, block_o=block_o, block_f=block_f,
    )
    return yr + 1j * yi


@span("sthc.mac")
def _mac_grouped_planes(
    xr: Array,
    xi: Array,
    pool_re: Array,
    pool_im: Array,
    o_start: Array,
    n_out: int,
    *,
    min_mxu_c: int | None,
    block_o: int | None,
    block_f: int | None,
) -> tuple[Array, Array]:
    """:func:`spectral_mac_grouped` on split (real, imaginary) planes.

    The arena's stored layout decides the bins' layout throughout: a
    5-D arena, (ΣO, C, FH, FW, FTr), is flattened here (and the kernel
    pads its bins to the lane tile at every call), and the output comes
    back 5-D; an arena of lane planes, (ΣO, C, R, L), takes the spectra
    as lane planes too, and the output is the kernel's own (B, n_out,
    R, L), which :func:`repro.core.spectral_conv.irfft3_lanes` reads as
    it lies."""
    tiles = _tile_kwargs(None, block_o, block_f)
    B, C = xr.shape[:2]
    fshape = xr.shape[2:]
    if pool_re.ndim == 5:
        f = 1
        for n in fshape:
            f *= n
        so = pool_re.shape[0]
        xr, xi = xr.reshape(B, C, f), xi.reshape(B, C, f)
        pool_re = pool_re.reshape(so, C, f)
        pool_im = pool_im.reshape(so, C, f)
    yr, yi = _kernel.spectral_mac_grouped_pallas(
        xr.astype(jnp.float32),
        xi.astype(jnp.float32),
        pool_re,
        pool_im,
        jnp.asarray(o_start, jnp.int32),
        n_out=int(n_out),
        min_mxu_c=min_mxu_c,
        interpret=_use_interpret(),
        **tiles,
    )
    out = (B, int(n_out), *fshape)
    return yr.reshape(out), yi.reshape(out)


def query_grating_pooled(
    x: Array,
    pool_re: Array,
    pool_im: Array,
    o_start: Array,
    n_out: int,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
    *,
    min_mxu_c: int | None = None,
    block_o: int | None = None,
    block_f: int | None = None,
) -> Array:
    """Pooled counterpart of :func:`query_grating_pallas`: one forward
    FFT over the stacked mixed-tenant batch, one grouped-kernel launch
    against the pooled arena, one inverse FFT — all on split real /
    imaginary planes, in the arena's bin layout (lane planes where the
    arena is stored so, see :func:`_mac_grouped_planes`)."""
    if pool_re.ndim == 4:
        rfft, irfft = spectral_conv.rfft3_lanes, spectral_conv.irfft3_lanes
    else:
        rfft, irfft = spectral_conv.rfft3_planes, spectral_conv.irfft3_planes
    xr, xi = rfft(x, fft_shape)
    yr, yi = _mac_grouped_planes(
        xr,
        xi,
        pool_re,
        pool_im,
        o_start,
        n_out,
        min_mxu_c=min_mxu_c,
        block_o=block_o,
        block_f=block_f,
    )
    return irfft(yr, yi, fft_shape, out_shape)


def pooled_query_shard(
    x: Array,
    pool_re: Array,
    pool_im: Array,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
    *,
    min_mxu_c: int | None = None,
    block_o: int | None = None,
    block_f: int | None = None,
) -> Array:
    """Shard-local full-arena fan-out: :func:`query_grating_pooled` with
    every clip row reading the local arena tile *whole* (zero offsets,
    ``n_out`` = the tile's row count).

    The grouped-MAC body of the engine's mesh executor: under
    ``shard_map`` each model-axis device holds one ``shard_rows`` tile
    of the pooled arena and contracts it against its data-shard's clip
    rows — no offsets cross a shard, no psum follows (each tenant's
    O-slice lives on exactly one tile by packing).  Callers must pass
    ``check_vma=False`` to ``jax.shard_map``: ``pallas_call`` has no
    varying-manual-axes rule, and this body is collective-free anyway.  Bitwise
    equal to the offset-gather dispatch at the corresponding rows — the
    per-(row, kernel, frequency) C-contraction is the same op sequence
    regardless of the tile's row offset.
    """
    rows = jnp.zeros((x.shape[0],), jnp.int32)
    return query_grating_pooled(
        x,
        pool_re,
        pool_im,
        rows,
        int(pool_re.shape[0]),
        fft_shape,
        out_shape,
        min_mxu_c=min_mxu_c,
        block_o=block_o,
        block_f=block_f,
    )


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
