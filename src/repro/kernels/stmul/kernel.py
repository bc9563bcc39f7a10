"""Pallas TPU kernel: spectral grating multiply-accumulate (STHC hot spot).

Computes, over flattened frequency bins f,

    Ŷ[b, o, f] = Σ_c  X̂[b, c, f] · G[o, c, f]        (complex)

with complex values carried as separate real/imag float planes (Pallas/TPU
has no native complex vregs).  In the fused query engine this runs once
per clip against the *effective* grating (± combine and static scales
pre-folded at record time) — the digital analogue of the optical
diffraction, where every atomic 'pixel' (frequency bin) scatters all
channels simultaneously.

Two kernel generations:

* **v1** (legacy, kept as a secondary oracle): the direct 4-real-multiply
  complex product as a VPU broadcast-MAC — ``(bB,1,C,bF)·(1,bO,C,bF)``
  elementwise, summed over C.
* **v2** (default): the 3-real-multiply (Karatsuba) complex trick

      t1 = Re(X)·Re(G),  t2 = Im(X)·Im(G),  t3 = (Re+Im)(X)·(Re+Im)(G)
      Re(Y) = t1 − t2,   Im(Y) = t3 − t1 − t2

  cutting real multiplies 4 → 3 (the adds ride the VPU for free), and —
  when C ≥ ``MIN_MXU_C`` — each ``tᵢ`` C-contraction is expressed as an
  f-batched ``jax.lax.dot_general`` over ``(bO, C) × (C, bB)`` tiles so
  Mosaic can route the contraction to the MXU instead of unrolling C on
  the VPU.  For small C (the paper's C=1 workload) the broadcast-MAC
  form is kept: a 1-deep matmul would waste the systolic array.

Grouped (pooled cross-tenant) variant
-------------------------------------
``spectral_mac_grouped_pallas`` contracts a whole *pooled* grating arena
in one launch: the gratings of every resident tenant are stacked on the
O axis (``(ΣO_pad, C, R, L)``) and each query row ``b`` reads only its own
tenant's O-slice, selected by a per-row block offset prefetched into
SMEM (``pltpu.PrefetchScalarGridSpec`` — the offset feeds the grating
BlockSpec's index map, so the right arena tile is DMA'd per program).
A mixed-tenant batch of N same-geometry tenants is thus one kernel
launch instead of N.  Arena planes may be stored bf16 (half-precision
grating storage); tiles are up-cast to f32 in-kernel so the contraction
accumulates in f32 either way.  The bins come as lane planes,
``(…, R, L)`` (``repro.core.spectral_conv.to_lane_planes``), the layout
of the resident arenas: tiles are whole rows of L lanes, and the output
is written in the layout the inverse transform reads.

Tiling
------
grid = (B/bB, O/bO, F/bF); each program reads
    x tile (bB, C, bF)  +  g tile (bO, C, bF)   → writes y tile (bB, bO, bF)
with bF a multiple of 128 (lane width).  VMEM per program ≈
(bB + bO)·C·bF·4B·2(planes) + bB·bO·bF·8B; defaults keep this ≈ 2 MiB,
well inside the ~16 MiB VMEM budget.  The grouped variant runs one
query row per program (bB = 1): rows of one batch may belong to
different tenants, so the row axis cannot tile without constraining the
scheduler to tenant-contiguous blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spans import span

Array = jax.Array

# Default tile sizes (see VMEM budget above).
BLOCK_B = 4
BLOCK_O = 8
# Largest O block of the grouped kernel: 16 rows of (96, 128) planes,
# double-buffered in and out, stay inside the default scoped VMEM.
MAX_BLOCK_O = 16
BLOCK_F = 512  # lanes; multiple of 128
# Lane-plane tiles: rows of the (R, L) bins per tile at C = 1 — one
# (96, 128) plane of the paper's window grid, 12,288 bins a step.
BLOCK_R = 96

# Contraction depth at which the MXU beats an unrolled VPU MAC.  This is
# the *default* routing threshold; callers tune it per deployment via the
# ``min_mxu_c`` argument (surfaced as ``STHCConfig.stmul_min_mxu_c`` and
# swept in ``benchmarks/kernels_bench.py``), so re-tuning on real TPU
# needs no code change.
MIN_MXU_C = 8

# Fused-readout (top-K reduction) tile defaults — the epilogue kernel
# that collapses a correlation window chunk to a tiny (rows, K) running
# state.  ``READOUT_BLOCK_L`` is the lane tile over the flattened
# (windows × H' × W' × step) score axis; surfaced as
# ``STHCConfig.readout_block_o`` / ``readout_block_l`` and swept in
# ``benchmarks/kernels_bench.py``.
READOUT_BLOCK_O = 8
READOUT_BLOCK_L = 512

# Sentinel index for an unfilled top-K slot (K exceeded the number of
# finite candidates, or the candidate set was NaN-poisoned).  Also the
# pad value for index tiles, so padding can never win a tie-break.
TOPK_EMPTY_IDX = 2**31 - 1  # jnp.iinfo(int32).max


def _stmul_kernel_v1(xr_ref, xi_ref, gr_ref, gi_ref, yr_ref, yi_ref):
    """One (bB, bO, bF) output tile; accumulate over the full C axis.

    Direct complex product: 4 real multiplies per (b, o, c, f).
    """
    xr = xr_ref[...]  # (bB, C, bF)
    xi = xi_ref[...]
    gr = gr_ref[...]  # (bO, C, bF)
    gi = gi_ref[...]
    # (bB, 1, C, bF) × (1, bO, C, bF) → sum over C → (bB, bO, bF).
    # Complex product: (xr+ixi)(gr+igi).
    yr = jnp.sum(xr[:, None] * gr[None] - xi[:, None] * gi[None], axis=2)
    yi = jnp.sum(xr[:, None] * gi[None] + xi[:, None] * gr[None], axis=2)
    yr_ref[...] = yr
    yi_ref[...] = yi


def _contract_c(x, g, use_mxu: bool):
    """Σ_c x[b, c, f] · g[o, c, f] → (bB, bO, bF) real contraction."""
    if use_mxu:
        # f-batched matmul: for every lane f, (bB, C) × (C, bO) — deep
        # enough C keeps the systolic array busy across the 128-lane batch.
        out = jax.lax.dot_general(
            x,
            g,
            dimension_numbers=(((1,), (1,)), ((2,), (2,))),
            preferred_element_type=jnp.float32,
        )  # (bF, bB, bO)
        return jnp.transpose(out, (1, 2, 0))
    # shallow C: broadcast-MAC on the VPU (no systolic fill/drain cost)
    return jnp.sum(x[:, None] * g[None], axis=2)


def _stmul_kernel_v2(xr_ref, xi_ref, gr_ref, gi_ref, yr_ref, yi_ref,
                     *, use_mxu: bool):
    """Karatsuba complex MAC: 3 real contractions instead of 4."""
    xr = xr_ref[...]  # (bB, C, bF)
    xi = xi_ref[...]
    gr = gr_ref[...]  # (bO, C, bF)
    gi = gi_ref[...]
    t1 = _contract_c(xr, gr, use_mxu)
    t2 = _contract_c(xi, gi, use_mxu)
    t3 = _contract_c(xr + xi, gr + gi, use_mxu)
    yr_ref[...] = t1 - t2
    yi_ref[...] = t3 - t1 - t2


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_b", "block_o", "block_f", "version", "min_mxu_c", "interpret",
    ),
)
def spectral_mac_pallas(
    xr: Array,
    xi: Array,
    gr: Array,
    gi: Array,
    *,
    block_b: int = BLOCK_B,
    block_o: int = BLOCK_O,
    block_f: int = BLOCK_F,
    version: int = 2,
    min_mxu_c: int | None = None,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Spectral MAC on real/imag planes.

    Args:
      xr, xi: (B, C, F) float32 — query spectrum planes.
      gr, gi: (O, C, F) float32 — grating planes.
      version: 1 = legacy 4-multiply VPU broadcast-MAC;
               2 = Karatsuba 3-multiply, MXU-routed contraction for
               C ≥ ``min_mxu_c``.
      min_mxu_c: v2 MXU routing threshold (None = module default
        ``MIN_MXU_C``); 1 forces the MXU path, a huge value forces the
        VPU broadcast-MAC — the tuning sweep knob for real-TPU runs.

    Returns (yr, yi): (B, O, F) float32.  F, B, O are padded to tile
    multiples internally and cropped on return.
    """
    B, C, F = xr.shape
    O = gr.shape[0]
    bB = min(block_b, B)
    bO = min(block_o, O)
    bF = min(block_f, F)

    def pad_to(a, axis, mult):
        n = a.shape[axis]
        rem = (-n) % mult
        if rem == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, rem)
        return jnp.pad(a, widths)

    xr_p = pad_to(pad_to(xr, 0, bB), 2, bF)
    xi_p = pad_to(pad_to(xi, 0, bB), 2, bF)
    gr_p = pad_to(pad_to(gr, 0, bO), 2, bF)
    gi_p = pad_to(pad_to(gi, 0, bO), 2, bF)
    Bp, _, Fp = xr_p.shape
    Op = gr_p.shape[0]

    threshold = MIN_MXU_C if min_mxu_c is None else int(min_mxu_c)
    if version == 1:
        kernel = _stmul_kernel_v1
    elif version == 2:
        kernel = functools.partial(_stmul_kernel_v2, use_mxu=C >= threshold)
    else:
        raise ValueError(f"unknown stmul kernel version {version!r}")

    grid = (Bp // bB, Op // bO, Fp // bF)
    x_spec = pl.BlockSpec((bB, C, bF), lambda b, o, f: (b, 0, f))
    g_spec = pl.BlockSpec((bO, C, bF), lambda b, o, f: (o, 0, f))
    y_spec = pl.BlockSpec((bB, bO, bF), lambda b, o, f: (b, o, f))

    yr, yi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, x_spec, g_spec, g_spec],
        out_specs=[y_spec, y_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Op, Fp), xr.dtype),
            jax.ShapeDtypeStruct((Bp, Op, Fp), xr.dtype),
        ],
        interpret=interpret,
    )(xr_p, xi_p, gr_p, gi_p)
    return yr[:B, :O, :F], yi[:B, :O, :F]


def grouped_block_o(stride: int) -> int:
    """O block of the grouped kernel over an arena whose member slots
    start every ``stride`` rows: the whole stride up to ``MAX_BLOCK_O``
    rows, else its largest divisor not above that — so every slot
    offset, and every ``n_out`` that is a multiple of the stride, sits
    on the block grid."""
    stride = int(stride)
    return max(
        d for d in range(1, min(stride, MAX_BLOCK_O) + 1) if stride % d == 0
    )


def _stmul_kernel_grouped(
    off_ref, xr_ref, xi_ref, gr_ref, gi_ref, yr_ref, yi_ref
):
    """One (1, bO, b_r, L) tile of the pooled contraction, C on the VPU.

    ``off_ref`` is the prefetched per-row block-offset vector — consumed
    by the grating BlockSpec's index map, not here.  Tiles up-cast to
    f32 (arena planes may be bf16) so accumulation is f32 either way.
    """
    xr = xr_ref[...].astype(jnp.float32)  # (1, C, b_r, L)
    xi = xi_ref[...].astype(jnp.float32)
    gr = gr_ref[...].astype(jnp.float32)  # (bO, C, b_r, L)
    gi = gi_ref[...].astype(jnp.float32)
    t1 = _contract_c(xr, gr, False)
    t2 = _contract_c(xi, gi, False)
    t3 = _contract_c(xr + xi, gr + gi, False)
    yr_ref[...] = t1 - t2
    yi_ref[...] = t3 - t1 - t2


@functools.partial(
    jax.jit,
    static_argnames=("n_out", "block_o", "block_f", "interpret"),
)
def spectral_mac_grouped_pallas(
    xr: Array,
    xi: Array,
    gr: Array,
    gi: Array,
    o_start: Array,
    *,
    n_out: int,
    block_o: int = BLOCK_O,
    block_f: int | None = None,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Grouped/ragged spectral MAC against a pooled grating arena.

        y[b, o, r, l] = Σ_c  x[b, c, r, l] · g[o_start[b] + o, c, r, l]

    — one launch contracts every query row against its own tenant's
    O-slice of the arena (per-row offsets via scalar prefetch).

    The bins come as lane planes, ``(…, R, L)`` with R a multiple of 8
    and L of 128 (``repro.core.spectral_conv.to_lane_planes``), which
    are read and written as they lie, in tiles of whole rows —
    ``block_f // L`` of them where ``block_f`` is given, else
    ``BLOCK_R // C`` (at least 8), cut to a divisor of R — with C
    contracted on the VPU.

    Args:
      xr, xi: (B, C, R, L) float32 query-spectrum planes.
      gr, gi: (ΣO_pad, C, R, L) float32 *or bfloat16* pooled arena
        planes (half-precision grating storage stays narrow in HBM;
        tiles up-cast in-kernel, f32 accumulation).
      o_start: (B,) int32 first-row offset per query row; every offset
        must sit on the ``block_o`` grid (the arena packs member slots
        every ``align`` rows and the engine passes its
        ``GratingPool.block_o``, ``grouped_block_o(align)``).
      n_out: rows read/written per query row (a multiple of the slot
        stride: one slot, or a union span of several).
      block_o: rows of the O block, a leading block dimension, so any
        size Mosaic can hold in VMEM (see ``grouped_block_o``).

    Returns (yr, yi): (B, n_out, R, L) float32.
    """
    if gr.ndim != 4 or xr.ndim != 4:
        raise ValueError(
            "the grouped MAC takes lane planes: arena (ΣO, C, R, L) and "
            f"spectra (B, C, R, L); got arena {tuple(gr.shape)} and "
            f"spectra {tuple(xr.shape)} (see "
            "repro.core.spectral_conv.to_lane_planes)"
        )
    B, C, rows, lanes = xr.shape
    bO = block_o
    n_pad = (-n_out) % bO

    def pad_to(a, axis, mult):
        rem = (-a.shape[axis]) % mult
        if rem == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, rem)
        return jnp.pad(a, widths)

    want = max(8, block_f // lanes if block_f else BLOCK_R // C)
    b_r = max(r for r in range(8, want + 1, 8) if rows % r == 0)
    assert rows % b_r == 0  # tiles of whole rows
    tile = (b_r, lanes)
    # row-pad the arena so the widest tile read (o_start + n_out_pad)
    # stays in bounds even for the last member slot
    gr = pad_to(gr, 0, bO)
    gi = pad_to(gi, 0, bO)
    if n_pad:
        widths = [(0, n_pad)] + [(0, 0)] * (gr.ndim - 1)
        gr = jnp.pad(gr, widths)
        gi = jnp.pad(gi, widths)
    n_out_pad = n_out + n_pad
    off_blocks = (o_start // bO).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_out_pad // bO, rows // b_r),
        in_specs=[
            pl.BlockSpec((1, C) + tile, lambda b, o, f, off: (b, 0, f, 0)),
            pl.BlockSpec((1, C) + tile, lambda b, o, f, off: (b, 0, f, 0)),
            pl.BlockSpec(
                (bO, C) + tile, lambda b, o, f, off: (off[b] + o, 0, f, 0)
            ),
            pl.BlockSpec(
                (bO, C) + tile, lambda b, o, f, off: (off[b] + o, 0, f, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, bO) + tile, lambda b, o, f, off: (b, o, f, 0)),
            pl.BlockSpec((1, bO) + tile, lambda b, o, f, off: (b, o, f, 0)),
        ],
    )
    out = jax.ShapeDtypeStruct((B, n_out_pad, rows, lanes), jnp.float32)
    yr, yi = pl.pallas_call(
        _stmul_kernel_grouped,
        grid_spec=grid_spec,
        out_shape=[out, out],
        interpret=interpret,
    )(off_blocks, xr, xi, gr, gi)
    if not n_pad:
        return yr, yi
    return yr[:, :n_out], yi[:, :n_out]


# ---------------------------------------------------------------------------
# Fused detection readout: top-K reduction of correlation scores
# ---------------------------------------------------------------------------
#
# The serving epilogue: instead of stitching per-window correlation
# outputs into the full (B, O, H', W', T') volume and reducing it on the
# host path, each window chunk is collapsed in-kernel to the K best
# (score, position) pairs per (row, output-kernel).  The running state is
# tiny — (B, O, K) floats + int32 positions — and merging two states is
# another top-K select, so the reduction is associative: chunked,
# re-chunked and one-shot streams produce bit-identical detections.


@span("sthc.readout")
def topk_select(vals: Array, gidx: Array, k: int) -> tuple[Array, Array]:
    """Top-k along the last axis with a *total* order: score descending,
    then global index ascending (ties go to the smallest index — exactly
    ``argmax``'s first-occurrence rule for k = 1).

    Pure jnp, shared verbatim by the Pallas readout kernel, the dense
    (no-Pallas) engine path and the cross-chunk/segment state merges, so
    every route produces bitwise-equal states.  Because the order is
    total (indices are unique), hierarchical selection is exact:
    ``topk(A ∪ B) == topk(topk(A) ∪ topk(B))``.

    NaN scores propagate: ``jnp.max`` returns NaN, the equality mask
    then matches nothing, and the slot's index degrades to the
    ``TOPK_EMPTY_IDX`` sentinel — a poisoned chunk yields NaN state
    scores for the signal-integrity guard to quarantine, never a
    silently wrong detection.

    Args:
      vals: (..., L) float32 scores.
      gidx: (..., L) int32 global positions, unique along the axis
        (``TOPK_EMPTY_IDX`` marks padding, paired with −inf scores).
      k: static number of survivors.

    Returns (scores, index): (..., k) each, sorted by the total order.
    """
    out_s, out_i = [], []
    big = jnp.asarray(TOPK_EMPTY_IDX, gidx.dtype)
    neg = jnp.asarray(-jnp.inf, vals.dtype)
    L = vals.shape[-1]
    # unique per-slot positions for the knock-out mask: gidx values are
    # unique for real entries but the TOPK_EMPTY_IDX sentinel (padding /
    # NaN-degraded slots) is not, and masking by value would wipe every
    # sentinel slot at once — merged states would then diverge from the
    # one-shot reduction on poisoned rows.
    pos = jax.lax.broadcasted_iota(jnp.int32, vals.shape, vals.ndim - 1)
    for _ in range(int(k)):
        m = jnp.max(vals, axis=-1, keepdims=True)
        hit = vals == m  # empty for NaN m: the slot saturates, no mask
        # smallest global index among the maximal positions; a −inf max
        # means the row is exhausted (k exceeded the candidates) — the
        # slot reports the empty sentinel, not a stale index
        sel = jnp.min(jnp.where(hit, gidx, big), axis=-1, keepdims=True)
        sel = jnp.where(m == neg, big, sel)
        out_s.append(m)
        out_i.append(sel)
        p = jnp.min(
            jnp.where(hit & (gidx == sel), pos, L), axis=-1, keepdims=True
        )
        vals = jnp.where(pos == p, neg, vals)
    return jnp.concatenate(out_s, -1), jnp.concatenate(out_i, -1)


def _topk_readout_kernel(v_ref, i_ref, s_ref, ix_ref, *, k: int):
    """One (1, bO, bL) score tile → the (1, bO, K) running state.

    Grid is (B, O/bO, L/bL) with L innermost; the output block is
    revisited across the L steps, so the state accumulates in-register:
    the first tile initializes it, every later tile merges its own
    top-k in (one more ``topk_select`` over 2K candidates).
    """
    vals = v_ref[0].astype(jnp.float32)  # (bO, bL)
    gidx = jnp.broadcast_to(i_ref[...], vals.shape)  # (1, bL) → (bO, bL)
    ts, ti = topk_select(vals, gidx, k)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[0] = ts
        ix_ref[0] = ti

    @pl.when(pl.program_id(2) != 0)
    def _merge():
        ms, mi = topk_select(
            jnp.concatenate([s_ref[0], ts], axis=-1),
            jnp.concatenate([ix_ref[0], ti], axis=-1),
            k,
        )
        s_ref[0] = ms
        ix_ref[0] = mi


@functools.partial(
    jax.jit, static_argnames=("k", "block_o", "block_l", "interpret")
)
def topk_readout_pallas(
    vals: Array,
    gidx: Array,
    *,
    k: int,
    block_o: int = READOUT_BLOCK_O,
    block_l: int = READOUT_BLOCK_L,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused detection readout: per-(row, kernel) top-k of a score axis.

    Args:
      vals: (B, O, L) float32 — a window chunk's correlation scores,
        flattened over (windows, H', W', step); padding must carry −inf.
      gidx: (L,) int32 — each element's global flat position in the
        stream's (H', W', T'valid) volume (shared by every (b, o) row);
        ``TOPK_EMPTY_IDX`` marks padding.
      k: state width (static).
      block_o / block_l: O/L tile sizes; L tiles stream through one
        resident output block per (b, o-block).

    Returns (scores, index): (B, O, k) f32 / int32, descending score,
    ascending-index tie-break — bitwise equal to ``topk_select`` over
    the whole axis.
    """
    B, O, L = vals.shape
    bO = min(int(block_o), O)
    bL = min(int(block_l), L)
    o_pad = (-O) % bO
    l_pad = (-L) % bL
    if o_pad or l_pad:
        vals = jnp.pad(
            vals, [(0, 0), (0, o_pad), (0, l_pad)],
            constant_values=-jnp.inf,
        )
    if l_pad:
        gidx = jnp.pad(gidx, [(0, l_pad)], constant_values=TOPK_EMPTY_IDX)
    Op, Lp = O + o_pad, L + l_pad

    grid = (B, Op // bO, Lp // bL)
    s, ix = pl.pallas_call(
        functools.partial(_topk_readout_kernel, k=int(k)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bO, bL), lambda b, o, l: (b, o, l)),
            pl.BlockSpec((1, bL), lambda b, o, l: (0, l)),
        ],
        out_specs=[
            pl.BlockSpec((1, bO, k), lambda b, o, l: (b, o, 0)),
            pl.BlockSpec((1, bO, k), lambda b, o, l: (b, o, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Op, int(k)), jnp.float32),
            jax.ShapeDtypeStruct((B, Op, int(k)), jnp.int32),
        ],
        interpret=interpret,
    )(vals.astype(jnp.float32), gidx.reshape(1, Lp).astype(jnp.int32))
    return s[:, :O], ix[:, :O]
