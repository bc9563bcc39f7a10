"""Fused weight-stationary spectral query engine — the STHC hot path.

The optical system's economics come from one asymmetry: the grating is
written **once** and then diffracts **many** clips per second.  The TPU
mapping must honor the same dataflow.  The seed implementation did not:
physical mode ran ``query_grating`` twice (once per pseudo-negative ±
grating), recomputing the identical ``rfftn(x)`` both times, and
``STHC.__call__`` re-recorded the grating on every invocation.

``QueryEngine`` fixes the dataflow at both ends:

* **Record** packs the ± gratings into one stacked tensor *and* folds
  everything static — the pseudo-negative combine (``G⁺ − G⁻``), the
  per-output-channel kernel de-quantization scale, and the photon-echo
  gain — into a single *effective* grating.  Diffraction is linear in
  the grating, so ``IFFT(X̂·G⁺) − IFFT(X̂·G⁻) ≡ IFFT(X̂·(G⁺ − G⁻))``
  exactly; the non-linear steps (SLM quantization of K⁺/K⁻) all happen
  at record time, before the fold.

* **Query** then computes exactly one forward ``rfftn`` per clip, one
  channel-contracted MAC against the effective grating (optionally the
  Pallas ``stmul`` kernel), and one inverse FFT — for physical mode
  this halves the FFT count and kernel launches versus the unfused ±
  path.  The only epilogue left at query time is the per-example query
  de-scaling, which depends on the clip itself.

* **Stream** — ``query_stream`` is the same fused path per coherence
  window (paper Fig. 1C): the grating is recorded once at the *window*
  FFT geometry and a long clip is pushed through overlap-save with the
  windowing math from :mod:`repro.core.spectral_conv`.  The window
  geometry fixes only the FFT numerics: the recorded *physics* (IHB and
  recording-pulse envelopes) live on the reference's own kt-point grid,
  so the grating is a pure function of the reference, independent of
  any query geometry.  Physical encoding uses a **stream-global** SLM
  scale — the modulator has one dynamic range for the whole stream, not
  one per window.  Together these make the streaming output equal to
  the one-shot physical correlation (tested property).

* **Pooled serving** — ``query_stream_many`` extends the
  weight-stationary dataflow *across tenants*: resident effective
  gratings that share FFT geometry and encode semantics are packed into
  one arena per pool group (:class:`GratingPool`, packed from the
  gratings declared by :meth:`QueryEngine.set_resident`, with a batch's
  composition — rows and their arena offsets — passed as runtime data,
  so any mix of resident tenants reuses one compiled program per row
  bucket) and a mixed-tenant clip batch is answered with
  exactly one forward FFT, one pooled channel-contracted MAC in which
  every clip row reads only its own tenant's O-offset slice, and one
  inverse FFT — N same-geometry tenants pay 1 device dispatch instead of
  N.  A one-shot clip is a stream of one window.  **Clip-dedup** takes the fan-out the rest of the way to the
  paper's headline dataflow (many kernels correlated against *one*
  stream in parallel): batch rows whose clips hash content-equal
  (:func:`clip_key`) collapse onto one physical row reading the union
  of their tenants' O-slices, so N tenants searching the same stream
  pay one forward FFT total, not N.  **Bounded-memory streaming**
  (``STHCConfig.osave_max_buffer_windows``) feeds streams longer than
  one device buffer through a
  :class:`~repro.core.spectral_conv.StreamCursor` in fixed-size
  T-chunks with kt−1-frame carry-over tails — constant peak memory,
  stream-global SLM scale, output exactly equal to one-shot.  Optional
  half-precision storage (``STHCConfig.grating_dtype = 'bfloat16'``)
  keeps gratings as split-real bf16 planes (half the HBM, ~2x the
  tenants per cache byte budget) with f32 accumulation at the MAC.

* **Fidelity** — the engine is *mode-agnostic*: it consumes the
  record-time and query-time transforms of the config's
  :class:`~repro.core.fidelity.FidelityPipeline` (an ordered stack of
  typed physics stages) instead of branching on a mode string.  An
  empty pipeline (``fidelity.ideal()``) records the exact kernel
  spectrum and skips the encode epilogue entirely; the full
  ``fidelity.physical()`` stack reproduces the paper's effect chain
  bit-for-bit against the pre-pipeline implementation (pinned tests);
  arbitrary subsets power the ablation benchmark and per-tenant
  mixed-fidelity serving.

* **Cache** — ``GratingCache`` memoizes recorded gratings under a
  content hash (kernel bytes + fft geometry + the pipeline fingerprint
  and device configs), so repeated ``STHC.__call__`` / ``hybrid`` /
  serving invocations with the same kernels stop re-recording.  The LRU budget is sized both in entries
  and in grating *bytes* (multi-tenant serving), with hit/miss/eviction
  counters surfaced via :meth:`GratingCache.stats`.  Tracer inputs
  (inside ``jit``) bypass the cache transparently.

The unfused two-query path is kept as ``query_unfused`` — it is the
reference the fused path is tested against, and the baseline the speed
benchmark compares with.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import fidelity as fidelity_mod
from repro.core import optics, pseudo_negative, spectral_conv
from repro.core.spans import span

if TYPE_CHECKING:  # avoid a circular import; sthc imports this module
    from repro.core.sthc import STHCConfig

Array = jax.Array


@dataclasses.dataclass
class FusedGrating:
    """Recorded state of the atomic medium, packed for fused queries.

    Attributes:
      stacked: (S, O, C, FH, FW, FTr) complex — the raw ± gratings as
        written (S=2, physical mode).  Kept for the unfused reference
        path and for introspection; the hot path never reads it.  In
        ideal mode there is nothing to stack (the effective grating IS
        the recording), so this is None and long-lived serving gratings
        hold a single tensor.
      effective: (O, C, FH, FW, FTr) complex — ``Σ_s w_s · stacked[s]``
        with the kernel de-quantization scale and echo gain folded in.
        This is the tensor held stationary in HBM (f32 storage mode).
        In half-precision storage mode (``STHCConfig.grating_dtype =
        'bfloat16'``) it is None and the recording lives in ``eff_re`` /
        ``eff_im`` instead; query paths go through :attr:`effective_c`,
        which serves either layout.
      eff_re / eff_im: split real/imag bf16 planes of the effective
        grating — the half-precision storage layout (complex64 has no
        narrow variant, so the planes are stored separately and up-cast
        to f32 at the MAC: bf16 at rest, f32 accumulation in compute).
        Half the HBM per grating, so a ``GratingCache`` byte budget
        holds ~2x the tenants.
      storage_dtype: 'float32' | 'bfloat16' — which layout holds the
        effective grating.
      fft_shape / out_shape: FFT grid and valid-region crop.
      kernel_scale: (O, 1, 1, 1, 1) de-quantization scale (already
        folded into ``effective``; kept for the reference path).
      echo_gain: scalar echo-efficiency factor (likewise folded).
      encode: whether queries must pass through the SLM model
        (non-negativity + per-example scale + quantization) — i.e. the
        record-time pipeline had query-encoding stages.
      slm_bits: SLM bit depth used for query encoding (resolved from
        the pipeline's quantize stage / the SLM config at record time).
      ker_shape: (kh, kw, kt) of the recorded kernels — with
        ``out_shape`` this pins the record-time signal geometry, which
        the streaming path needs to derive its window length.
      pseudo_negative: the recording ± split signed kernels and folded
        ``G⁺ − G⁻`` — i.e. a stacked pair existed at record time even
        if ``keep_stacked=False`` dropped it.  The unfused reference
        path uses this to distinguish "nothing to unfuse" from "the ±
        stack was discarded".
    """

    stacked: Array | None
    effective: Array | None
    fft_shape: tuple[int, int, int]
    out_shape: tuple[int, int, int]
    kernel_scale: Array
    echo_gain: Array
    encode: bool = False
    slm_bits: int = 8
    ker_shape: tuple[int, int, int] | None = None
    pseudo_negative: bool = False
    eff_re: Array | None = None
    eff_im: Array | None = None
    storage_dtype: str = "float32"

    @property
    def effective_c(self) -> Array:
        """The query-ready complex64 effective grating, whichever layout
        stores it.  For f32 storage this is the stored tensor itself (no
        copy, bit-identical paths); bf16 storage up-casts the split-real
        planes — the one place half-precision re-enters f32 compute."""
        if self.effective is not None:
            return self.effective
        return lax.complex(
            self.eff_re.astype(jnp.float32), self.eff_im.astype(jnp.float32)
        )

    @property
    def planes(self) -> tuple[Array, Array]:
        """(re, im) planes in the storage dtype — what the pooled arena
        packs (bf16 gratings stay bf16 in HBM until the kernel's tile
        up-cast; f32 gratings split lazily)."""
        if self.effective is None:
            return self.eff_re, self.eff_im
        return jnp.real(self.effective), jnp.imag(self.effective)

    @property
    def n_out(self) -> int:
        """Output channels O recorded in this grating."""
        eff = self.effective if self.effective is not None else self.eff_re
        return int(eff.shape[0])

    @property
    def channels(self) -> int:
        """Input channels C the grating contracts over."""
        eff = self.effective if self.effective is not None else self.eff_re
        return int(eff.shape[1])

    @property
    def nbytes(self) -> int:
        """HBM footprint of the recorded state (cache byte accounting)."""
        if self.effective is not None:
            n = int(self.effective.nbytes)
        else:
            n = int(self.eff_re.nbytes) + int(self.eff_im.nbytes)
        if self.stacked is not None:
            n += int(self.stacked.nbytes)
        return n

    # -- backward-compatible views of the seed `Grating` layout ----------

    @property
    def plus(self) -> Array:
        return self.effective_c if self.stacked is None else self.stacked[0]

    @property
    def minus(self) -> Array | None:
        return None if self.stacked is None else self.stacked[1]


@dataclasses.dataclass(frozen=True)
class GratingPool:
    """A packed cross-tenant arena of effective gratings (one pool group).

    The serving counterpart of the paper's parallel-kernel recording:
    every resident tenant's effective grating is stacked into one
    ``(ΣO_pad, C, FH, FW, FTr)`` tensor held stationary on device, so a
    mixed-tenant clip batch diffracts off *all* of them in a single
    dispatch — each clip row reads only its own tenant's O-slice via its
    :attr:`o_start` offset.

    Attributes:
      re / im: split real/imag planes of the arena, in the members'
        storage dtype (bf16 gratings stay bf16 in HBM; the MAC up-casts
        tiles to f32 — f32 accumulation either way): lane planes
        (ΣO_pad, C, FTr·Hp, Wp) (``spectral_conv.to_lane_planes``)
        where the grouped Pallas kernel serves the arena, else the
        dense path's (ΣO_pad, C, FH, FW, FTr).  On a mesh they are
        placed with their rows sharded over the model axis.
      o_start: per-member first-row offset.  Member slots are padded to
        multiples of ``align`` rows, the slot stride: the members' own
        kernel count where they all have the same one (no zero rows),
        else the grouped Pallas kernel's ``BLOCK_O`` grid; the dense
        gather path uses align=1 (:meth:`QueryEngine._slot_layout`).
        The arena carries enough tail rows that every ``o_start[i] +
        n_out`` read stays in bounds.
      n_out: the widest member slot.
      align: the slot stride.
      block_o: the grouped Pallas kernel's O block, a divisor of
        ``align`` (None on the dense path).
      members: strong references to the member gratings — the arena is a
        pure repack of their planes, and pinning them keeps the
        identity-keyed slot map sound.
      shards: number of equal-row arena shards the packing respects
        (mesh serving).  ``shards > 1`` bins members into ``shards``
        equal tiles of ``shard_rows`` rows each (greedy least-loaded,
        deterministic), every member slot entirely inside one tile —
        a tenant's O-slice lives on exactly one device of the model
        axis, so the sharded MAC and fused readout stay psum-free.
    """

    re: Array
    im: Array
    o_start: tuple[int, ...]
    n_out: int
    align: int
    members: tuple[FusedGrating, ...]
    shards: int = 1
    block_o: int | None = None

    @property
    def shard_rows(self) -> int:
        """Arena rows per shard tile (= total rows when unsharded)."""
        return int(self.re.shape[0]) // int(self.shards)

    @property
    def nbytes(self) -> int:
        return int(self.re.nbytes) + int(self.im.nbytes)


@dataclasses.dataclass(frozen=True)
class _DedupLayout:
    """Row layout of one pool-group dispatch after clip-dedup.

    Attributes:
      uniq: group-local request index owning each physical clip copy
        (first requester of that content), in dispatch batch order.
      uniq_of: per group-local request — which physical copy serves it.
      row_of: per physical copy — its arena start row (the union span's
        first row, or earlier where the span would overrun the arena).
      o_off: per group-local request — offset of its tenant's O-slice
        inside its physical row's read.
      n_out: rows every physical row reads/writes (the widest span,
        a whole number of the pool's slot strides).
    """

    uniq: list[int]
    uniq_of: list[int]
    row_of: list[int]
    o_off: list[int]
    n_out: int


def _unique_rows(keys: list) -> tuple[list[int], list[int]]:
    """(uniq, uniq_of) of a group: the first request of each distinct
    clip, and for each request its physical copy.  A None key (no clip
    identity) is never shared."""
    uniq: list[int] = []
    uniq_of: list[int] = []
    by_key: dict[tuple, int] = {}
    for j, k in enumerate(keys):
        u = by_key.get(k) if k is not None else None
        if u is None:
            u = len(uniq)
            uniq.append(j)
            if k is not None:
                by_key[k] = u
        uniq_of.append(u)
    return uniq, uniq_of


def _span_layout(
    starts: list[int],
    widths: list[int],
    keys: list,
    align: int,
    arena_rows: int,
) -> _DedupLayout:
    """Collapse group rows with content-equal clips onto shared
    physical rows.

    ``starts[j]`` is request j's first arena row, ``widths[j]`` its
    tenant's O.  Each unique clip gets one physical row whose O-window
    is the *union span* of every slice requested for that clip (member
    slots pack contiguously, so the span is one aligned ``[lo, lo +
    n_out)`` read; tenants between two requested slots are computed and
    discarded — in the canonical all-tenants-one-stream batch the span
    is exactly the arena).  ``n_out`` is the widest span, rounded up to
    a bucket (:func:`_row_bucket`) of the arena's slot strides
    (``align``), so the pooled stream programs see few distinct widths
    and a span of whole slots is read with no zero rows.  A span that
    would read past the arena's last row starts earlier instead and its
    requests' ``o_off`` grow by the shift, so every read stays inside
    the arena (``n_out`` never exceeds it).

    One ``n_out`` for the whole dispatch is a deliberate trade-off: the
    MAC needs a uniform per-row width, so in a *mixed* batch (one wide
    shared-stream span next to narrow unique rows) the narrow rows
    compute and discard up to the widest span; splitting them into
    separate dispatches would cost an extra FFT dispatch per batch —
    the thing pooling exists to avoid.
    """
    uniq, uniq_of = _unique_rows(keys)
    span_lo: list = [None] * len(uniq)
    span_hi = [0] * len(uniq)
    for j, u in enumerate(uniq_of):
        s = starts[j]
        span_lo[u] = s if span_lo[u] is None else min(span_lo[u], s)
        span_hi[u] = max(span_hi[u], s + widths[j])
    tiles = -(-max(hi - lo for lo, hi in zip(span_lo, span_hi)) // align)
    n_out = min(_row_bucket(tiles) * align, arena_rows)
    row_of = [min(lo, arena_rows - n_out) for lo in span_lo]
    o_off = [starts[j] - row_of[uniq_of[j]] for j in range(len(uniq_of))]
    return _DedupLayout(
        uniq=uniq, uniq_of=uniq_of, row_of=row_of, o_off=o_off, n_out=n_out
    )


def _fanout_layout(
    starts: list[int], keys: list, arena_rows: int
) -> _DedupLayout:
    """Row layout of a mesh-sharded dispatch: full-arena fan-out.

    With the arena's ΣO rows sharded over the model axis, the
    offset-gather behind :func:`_span_layout`'s union spans would be a
    cross-shard read; instead every physical clip row computes against
    the *entire* (sharded) arena — each model-axis device contracts only
    its own ``shard_rows`` tile, psum-free — and a request's answer is
    the slice of the global output at its member slot's absolute start.
    Clip-dedup degenerates to unique-clips-only (a shared physical row
    already reads every tenant's slice).
    """
    uniq, uniq_of = _unique_rows(keys)
    return _DedupLayout(
        uniq=uniq,
        uniq_of=uniq_of,
        row_of=[0] * len(uniq),
        o_off=list(starts),
        n_out=int(arena_rows),
    )


def _dedup_members(
    gratings: list[FusedGrating],
) -> tuple[list[FusedGrating], list[int]]:
    """Unique member gratings (identity, first-seen order) + each
    request's member slot — two requests for one tenant share a slice."""
    members: list[FusedGrating] = []
    index: dict[int, int] = {}
    slot_of: list[int] = []
    for g in gratings:
        slot = index.get(id(g))
        if slot is None:
            slot = index[id(g)] = len(members)
            members.append(g)
        slot_of.append(slot)
    return members, slot_of


def _bin_members(slots: list[int], shards: int) -> tuple[list[int], int]:
    """Greedy least-loaded binning of member slot widths into ``shards``
    equal arena tiles.

    Returns (bin_of, shard_rows): each member's tile index (first-seen
    order, ties broken by lowest tile index — deterministic) and the
    per-tile row count
    (the max tile load, rounded up so every tile is the same height).
    """
    load = [0] * shards
    bin_of = []
    for s in slots:
        b = min(range(shards), key=lambda i: (load[i], i))
        bin_of.append(b)
        load[b] += s
    return bin_of, max(load) if load else 0


def _build_pool(
    members: list[FusedGrating],
    align: int,
    shards: int = 1,
    lanes: bool = False,
    block_o: int | None = None,
) -> GratingPool:
    """Pack member gratings' planes into one arena (see GratingPool).

    ``lanes`` packs the planes as lane planes
    (:func:`spectral_conv.to_lane_planes`), the one bin layout that the
    grouped Pallas kernel takes and the inverse transform reads as it
    is stored; without it the arena keeps the gratings' 5-D bins, which
    the dense path gathers from.

    Each member's slot is its rows rounded up to a multiple of
    ``align``, the slot stride; ``block_o`` is kept for the grouped
    kernel (:meth:`QueryEngine._slot_layout`).

    ``shards > 1`` makes the packing mesh-aware: members are binned
    into ``shards`` equal tiles of ``shard_rows`` rows (every tile
    zero-padded to the same height, ``shard_rows`` a multiple of
    ``align``), and no member slot straddles a tile boundary — slicing
    the arena into ``shards`` row-contiguous pieces puts each tenant's
    O-slice wholly on one model-axis device.
    """
    c = members[0].channels
    for g in members[1:]:
        if g.channels != c:
            raise ValueError(
                "pool members disagree on input channels: "
                f"{[m.channels for m in members]}"
            )
    planes = [g.planes for g in members]
    if lanes:
        planes = [
            spectral_conv.to_lane_planes(re, im, g.fft_shape)
            for (re, im), g in zip(planes, members)
        ]
    slots = [
        -(-int(re.shape[0]) // align) * align for re, _ in planes
    ]
    n_out = max(slots)

    def padded(i: int) -> tuple[Array, Array]:
        re, im = planes[i]
        if slots[i] > re.shape[0]:
            widths = [(0, slots[i] - re.shape[0])] + [(0, 0)] * (re.ndim - 1)
            re, im = jnp.pad(re, widths), jnp.pad(im, widths)
        return re, im

    res, ims = [], []
    feat = planes[0][0].shape[1:]
    dtype = planes[0][0].dtype
    if shards <= 1:
        # each slot as its planes plus a zero block, one concatenate for
        # the whole arena: no padded copy of a member is ever made
        o_start = []
        row = 0
        zeros: dict[int, Array] = {}
        for i, (re, im) in enumerate(planes):
            res.append(re)
            ims.append(im)
            gap = slots[i] - int(re.shape[0])
            if gap:
                if gap not in zeros:
                    zeros[gap] = jnp.zeros((gap,) + feat, dtype)
                res.append(zeros[gap])
                ims.append(zeros[gap])
            o_start.append(row)
            row += slots[i]
        tail = max(o + n_out for o in o_start) - row
        if tail > 0:  # keep the last members' n_out-row reads in bounds
            zeros = jnp.zeros((tail,) + feat, dtype)
            res.append(zeros)
            ims.append(zeros)
    else:
        bin_of, shard_rows = _bin_members(slots, shards)
        o_start = [0] * len(members)
        for b in range(shards):
            row = b * shard_rows
            for i, tile in enumerate(bin_of):
                if tile != b:
                    continue
                re, im = padded(i)
                res.append(re)
                ims.append(im)
                o_start[i] = row
                row += slots[i]
            tail = (b + 1) * shard_rows - row
            if tail > 0:  # equal-height tiles: zero-fill this shard
                zeros = jnp.zeros((tail,) + feat, dtype)
                res.append(zeros)
                ims.append(zeros)
    re = res[0] if len(res) == 1 else jnp.concatenate(res, axis=0)
    im = ims[0] if len(ims) == 1 else jnp.concatenate(ims, axis=0)
    return GratingPool(
        re=re,
        im=im,
        o_start=tuple(o_start),
        n_out=n_out,
        align=align,
        members=tuple(members),
        shards=max(1, int(shards)),
        block_o=block_o,
    )


def clip_key(x) -> tuple | None:
    """Content fingerprint of a clip batch — the shared-stream identity.

    Two requests whose clips hash equal (bytes + shape + dtype) are the
    *same stream*: the pooled executor answers them with one forward FFT
    over one physical copy, each tenant reading its own O-slice of the
    union span (see :meth:`QueryEngine.query_stream_many`).  Hashing is the
    point, not an optimization hazard: a false "same clip" would answer
    one tenant with another's stream, so the full buffer is digested
    (SHA-1), never a sample.  Tracers (inside ``jit``) have no bytes to
    hash and return None — such requests are never deduped.
    """
    if isinstance(x, jax.core.Tracer):
        return None
    arr = np.asarray(x)
    return (
        hashlib.sha1(arr.tobytes()).hexdigest(),
        arr.shape,
        str(arr.dtype),
    )


def _stream_scale(x) -> Array:
    """Stream-global SLM scale (one modulator dynamic range per example
    for the entire stream), matching ``QueryEngine._encode`` bit for
    bit.  Computed where the stream lives: host-side for np arrays (the
    bounded-memory serving path keeps long streams off-device), on
    device for jax arrays."""
    if isinstance(x, np.ndarray):
        a = np.maximum(x, 0).reshape(x.shape[0], -1).max(axis=1)
        a = np.where(a > 0, a, x.dtype.type(1))
        return jnp.asarray(a.reshape(-1, 1, 1, 1, 1))
    a = jnp.maximum(x, 0.0)
    a = jnp.max(a, axis=(1, 2, 3, 4), keepdims=True)
    return jnp.where(a > 0, a, 1.0)


def clip_keys_for(arrays) -> list:
    """Per-array clip identities, memoized by object identity within the
    call (one hash per distinct buffer, however many requests share it).
    The one fingerprinting loop behind both the engine's dedup grouping
    and the server's group-key construction."""
    memo: dict[int, tuple] = {}
    keys = []
    for x in arrays:
        k = memo.get(id(x))
        if k is None:
            k = clip_key(x)
            if k is not None:
                memo[id(x)] = k
        keys.append(k)
    return keys


def _row_bucket(n: int) -> int:
    """Rows a pooled stream program is compiled for: ``n`` rounded up to
    the next of 1, 2, 3, 4, 6, 8, 12, 16, … (powers of two and 1.5×
    them), so a pool group's programs number about two per doubling of
    the batch however its batches are composed, and padding stays
    under a third of a dispatch's rows.  (Powers of two alone pad a
    fifth of the rows of the Zipf-skewed cell, and make its work per
    batch vary with the mix three times as much.)"""
    n = max(int(n), 1)
    p = 1 << (n - 1).bit_length()  # the power of two at or above n
    return p * 3 // 4 if p >= 4 and n <= p * 3 // 4 else p


def _arena_key(g: FusedGrating) -> tuple:
    """The pool group a grating's resident arena belongs to: gratings
    that share FFT geometry, encode semantics, storage and channels."""
    return (
        g.fft_shape,
        g.out_shape,
        g.ker_shape,
        bool(g.encode),
        int(g.slm_bits) if g.encode else -1,
        g.storage_dtype,
        g.channels,
    )


@dataclasses.dataclass(frozen=True)
class _Arena:
    """A pool group's resident arena and where each member sits in it.

    Attributes:
      pool: the packed arena; its members are the declared residents of
        the group (:meth:`QueryEngine.set_resident`), in declaration
        order, then any other gratings the last rebuild had to admit.
        A mesh's arena is shard-tiled over its model axis and its planes
        are placed there.
      slot: ``id(grating) -> member index`` (the pool pins every member,
        so ids stay unique while the arena lives).
      declared: ids of the declared residents it was built from.
    """

    pool: GratingPool
    slot: dict
    declared: tuple


@dataclasses.dataclass(frozen=True)
class PooledTopK:
    """One request's answer inside its pool group's whole top-K state.

    The pooled stream programs return one ``(rows, n_out, K)`` state per
    pool group; a request owns ``scores[rows, kernels]``.  Callers that
    copy the state to the host (the video-search server) copy it once
    per group and slice there; :meth:`take` slices on the device."""

    scores: Array  # (rows, n_out, K): the whole group state
    index: Array
    out_shape: tuple[int, int, int]
    rows: slice
    kernels: slice

    def take(self) -> "TopKDetections":
        sl = (self.rows, self.kernels)
        return TopKDetections(self.scores[sl], self.index[sl], self.out_shape)


def _pool_select(
    pool_re: Array, pool_im: Array, rows: Array, n_out: int
) -> Array:
    """Per-row O-slices of the arena, as one complex64 tensor
    (B, n_out, C, FH, FW, FTr): clip row b sees arena rows
    ``[rows[b], rows[b] + n_out)``.  The planes up-cast to f32 here, so
    bf16-stored pools accumulate in f32 at the MAC.  Window-independent:
    streaming hoists this gather out of the overlap-save loop."""
    arena = lax.complex(
        pool_re.astype(jnp.float32), pool_im.astype(jnp.float32)
    )
    o_idx = rows[:, None] + jnp.arange(n_out, dtype=rows.dtype)[None, :]
    return arena[o_idx]


def _presel_query_dense(
    x: Array,
    sel: Array,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
) -> Array:
    """Pooled MAC on pre-selected per-row slices: exactly one forward
    ``rfftn`` over the stacked clip batch, one channel-contracted MAC,
    one ``irfftn`` (the XLA reference for the grouped Pallas kernel)."""
    xhat = spectral_conv.rfft3(x, fft_shape)
    with span("sthc.mac"):
        yhat = jnp.einsum(
            "bcxyz,bocxyz->boxyz", xhat, sel, precision="highest"
        )
    return spectral_conv.irfft3(yhat, fft_shape, out_shape)


# ---------------------------------------------------------------------------
# Fused detection readout — the streaming top-K state
# ---------------------------------------------------------------------------

# Sentinel for an unfilled/poisoned top-K slot (int32 max, matching
# kernels.stmul.kernel.TOPK_EMPTY_IDX without importing Pallas eagerly).
TOPK_EMPTY_IDX = 2**31 - 1
# Rows of a float32 (8, 128) TPU tile: the readout's folded row axis is
# padded to a whole number of them before its relayout.
_SUBLANES = 8


@dataclasses.dataclass(frozen=True)
class TopKDetections:
    """The fused-readout running state: per (clip row, output kernel),
    the K best correlation peaks of a stream — all a detection consumer
    needs, at O(K) memory instead of the O(H'·W'·T') stitched volume.

    ``index`` holds each peak's global flat position in the C-order
    ``(H', W', T'valid)`` valid-output volume, so ``peak_scores()[...,0]``
    / ``index[..., 0]`` equal ``volume.reshape(B, O, -1).max(-1)`` /
    ``argmax(-1)`` bitwise (ties resolve to the smallest flat index —
    argmax's first-occurrence rule).  ``TOPK_EMPTY_IDX`` marks a slot
    with no detection (K exceeded the volume, or the row's scores were
    NaN-poisoned — the scores stay NaN for the serving guard).  int32
    positions bound the addressable volume at 2³¹ elements (≈ 2.7M
    frames at the paper's 31×25 window); beyond that, shard the stream.

    Slicing rows/kernels commutes with the reduction, so dedup
    union-span states slice per request exactly like volumes do.
    """

    scores: Array  # (B, O, K) float32, descending
    index: Array  # (B, O, K) int32 global flat positions
    out_shape: tuple[int, int, int]  # (H', W', T'valid) of the stream

    @property
    def k(self) -> int:
        return int(self.scores.shape[-1])

    def peak_scores(self) -> Array:
        """(B, O) — bitwise ``max`` of the stitched volume."""
        return self.scores[..., 0]

    def peak_index(self) -> Array:
        """(B, O) — bitwise ``argmax`` of the flattened stitched volume."""
        return self.index[..., 0]

    def positions(self) -> tuple[Array, Array, Array]:
        """Decompose ``index`` into (t, h, w) int32 arrays, each
        (B, O, K).  ``t`` is the stream frame of the peak (the
        photon-echo peak position) — ``index % T'``, matching the
        serving contract."""
        Hp, Wp, Tv = self.out_shape
        t = self.index % Tv
        hw = self.index // Tv
        return t, hw // Wp, hw % Wp

    def __getitem__(self, sl) -> "TopKDetections":
        return TopKDetections(self.scores[sl], self.index[sl], self.out_shape)


def _rebase_topk_index(
    idx: Array, nv_local: int, t0: int, nv_total: int
) -> Array:
    """Rebase segment-local flat positions into the stream-global volume.

    A cursor segment reduces over its own ``(H', W', nv_local)`` grid;
    globally the same element sits at temporal offset ``t0``.  The local
    order (hw, t) is preserved (``t0 + t < nv_total`` for every valid
    element), so in-segment tie-breaks taken on local indices agree with
    the global total order — the rebased merge is exact.  Sentinel slots
    stay sentinels."""
    big = jnp.asarray(TOPK_EMPTY_IDX, idx.dtype)
    hw = idx // nv_local
    t = idx % nv_local
    return jnp.where(idx == big, big, hw * nv_total + t0 + t)


def _merge_topk_states(
    states: "list[tuple[Array, Array]]", k: int
) -> tuple[Array, Array]:
    """Exact associative merge of (scores, index) top-K states — one
    ``topk_select`` over the concatenated candidates (pure jnp; bitwise
    equal regardless of grouping or order)."""
    from repro.kernels.stmul import kernel as stmul_kernel  # lazy

    s = jnp.concatenate([st[0] for st in states], axis=-1)
    i = jnp.concatenate([st[1] for st in states], axis=-1)
    return stmul_kernel.topk_select(s, i, int(k))


def _segments_rebase_merge(
    seg_s, seg_i, *, k: int, nv_locals: tuple, t0s: tuple, nv_total: int
) -> tuple[Array, Array]:
    """Rebase every cursor segment's local top-K state into the
    stream-global index space and merge, as ONE traced computation.

    Done eagerly this is dozens of tiny host dispatches per request
    (4 ops per segment rebase + the concat/select merge), which at
    firehose segment counts costs more than the correlation itself —
    jitted, the whole tail collapses to a single launch over the tiny
    (B, O, K) states.  Segment geometry (local valid counts, global
    offsets) is static so the trace is shared across requests and
    batches with the same cursor layout."""
    states = [
        (s, _rebase_topk_index(i, nv, t0, nv_total))
        for s, i, nv, t0 in zip(seg_s, seg_i, nv_locals, t0s)
    ]
    return _merge_topk_states(states, int(k))


class QueryEngine:
    """Record-once / query-many executor for one :class:`STHCConfig`."""

    # row shapes whose zero padding rows stay on the device
    _max_zero_rows = 8

    def __init__(self, config: "STHCConfig"):
        self.config = config
        # the one-shot query (encode, forward transform, MAC, cropped
        # inverse transform, de-scaling) as one program per clip shape
        # and geometry: one host dispatch per call, the DFT matrices
        # compile-time constants, and no complex64 spectrum written out
        # between the MAC and the transforms
        self._query_one_fn = jax.jit(
            self._query_impl,
            static_argnames=("fft_shape", "out_shape", "encode", "slm_bits"),
        )
        self._trace_lock = threading.Lock()
        self._query_traces = 0  # guarded-by: _trace_lock
        # jitted overlap-save driver; built eagerly (wrapper creation is
        # free, tracing happens on first call) so concurrent first
        # queries from server threads can't race a lazy init
        self._stream_fn = jax.jit(
            self._stream_impl,
            static_argnames=(
                "ker_shape", "fft_shape", "plan", "encode", "slm_bits",
            ),
        )
        # pooled streaming driver.  A batch's composition (which tenants,
        # how many rows, where each row reads in the arena) is runtime
        # data: the rows arrive as a tuple of single-row clips padded to
        # a row bucket (_row_bucket), the per-row arena offsets as an int32
        # array, and the program returns the group's whole state, which
        # callers split per request.  Only shapes and geometry are
        # static, so one pool group compiles once per (bucket, n_out)
        # and any mix of resident tenants reuses those programs
        # (:attr:`stream_traces` counts their traces).
        self._stream_many_fn = jax.jit(
            self._stream_many_impl,
            static_argnames=(
                "ker_shape", "fft_shape", "plan", "encode", "slm_bits",
                "n_out", "block_o",
            ),
        )
        # fused-readout overlap-save drivers: same window loop, but each
        # chunk collapses to a (rows, K) top-K state in the epilogue —
        # the (B, O, H', W', T') volume never materializes (readout_k on
        # query_stream / query_stream_many)
        self._stream_topk_fn = jax.jit(
            self._stream_topk_impl,
            static_argnames=(
                "ker_shape", "fft_shape", "plan", "encode", "slm_bits", "k",
            ),
        )
        self._stream_many_topk_fn = jax.jit(
            self._stream_many_topk_impl,
            static_argnames=(
                "ker_shape", "fft_shape", "plan", "encode", "slm_bits",
                "n_out", "block_o", "k",
            ),
        )
        self._stream_traces = 0  # guarded-by: _trace_lock
        # cross-segment state tail (rebase + merge) as one launch — the
        # cursor path's per-request epilogue
        self._seg_merge_fn = jax.jit(
            _segments_rebase_merge,
            static_argnames=("k", "nv_locals", "t0s", "nv_total"),
        )
        # the pooled stream path's resident arenas, one per pool group
        # (_arena_key) and mesh (None on one device), packed from the
        # declared residents (set_resident) and rebuilt only when those
        # change or a batch brings an undeclared grating
        self._resident: dict[tuple, list[FusedGrating]] = {}  # guarded-by: _pools_lock
        self._arenas: dict[tuple, _Arena] = {}  # guarded-by: _pools_lock
        self._arena_builds = 0  # guarded-by: _pools_lock
        # zero rows that pad a row batch up to its bucket, one per row
        # shape, the last few shapes kept (device-resident: padding
        # uploads nothing)
        self._zero_rows: OrderedDict[tuple, Array] = OrderedDict()  # guarded-by: _pools_lock
        # per-Mesh jitted sharded drivers (a server owns one mesh per
        # replica, so this stays tiny)
        self._mesh_jits: dict = {}  # guarded-by: _pools_lock
        self._pools_lock = threading.Lock()
        # shared-stream fan-out accounting (clip-dedup in the pooled
        # paths): offered = clip rows requested, dispatched = physical
        # rows after collapsing same-content clips onto shared rows.
        self._pooled_dispatches = 0  # guarded-by: _pools_lock
        self._pooled_rows_offered = 0  # guarded-by: _pools_lock
        self._pooled_rows_dispatched = 0  # guarded-by: _pools_lock
        self._pooled_rows_padded = 0  # guarded-by: _pools_lock
        # kernel rows: those the requests of each physical row asked for
        # (a tenant counted once per row) and those it computed (n_out)
        self._kernel_rows_requested = 0  # guarded-by: _pools_lock
        self._kernel_rows_dispatched = 0  # guarded-by: _pools_lock
        # pooled dispatches of the grouped Pallas kernel, whose MAC
        # output the inverse transform reads in the kernel's own layout
        self._native_layout_dispatches = 0  # guarded-by: _pools_lock

    def pool_stats(self) -> dict:
        """Pooled-executor counters for serving metrics: how many clip
        rows the dedup collapsed (``rows_saved``) out of those offered;
        rows that carried a request (``rows_dispatched``) and rows that
        only padded a batch to its bucket (``rows_padded``); resident
        arenas packed (``arena_builds``) and pooled stream programs
        traced (``stream_traces``); dispatches of the grouped Pallas
        kernel, whose lane-plane output the inverse transform reads as
        it lies (``native_layout_dispatches``, 0 on the dense path); and
        over the rows that carried a request, the kernels their requests
        asked for, a tenant counted once per row
        (``kernel_rows_requested``), against the kernel rows computed,
        ``n_out`` per row (``kernel_rows_dispatched``)."""
        with self._trace_lock:
            traces = self._stream_traces
        with self._pools_lock:
            offered = self._pooled_rows_offered
            dispatched = self._pooled_rows_dispatched
            return {
                "dispatches": self._pooled_dispatches,
                "rows_offered": offered,
                "rows_dispatched": dispatched,
                "rows_saved": offered - dispatched,
                "rows_padded": self._pooled_rows_padded,
                "arena_builds": self._arena_builds,
                "stream_traces": traces,
                "native_layout_dispatches": self._native_layout_dispatches,
                "kernel_rows_requested": self._kernel_rows_requested,
                "kernel_rows_dispatched": self._kernel_rows_dispatched,
            }

    def _count_pooled(
        self, offered: int, dispatched: int, padded: int,
        kernels_requested: int, kernels_dispatched: int, native: bool,
    ) -> None:
        with self._pools_lock:
            self._pooled_dispatches += 1
            self._native_layout_dispatches += int(native)
            self._pooled_rows_offered += int(offered)
            self._pooled_rows_dispatched += int(dispatched)
            self._pooled_rows_padded += int(padded)
            self._kernel_rows_requested += int(kernels_requested)
            self._kernel_rows_dispatched += int(kernels_dispatched)

    # -- record -----------------------------------------------------------

    def record(
        self, kernels: Array, signal_shape: tuple[int, int, int]
    ) -> FusedGrating:
        """Write a kernel stack (O, C, kh, kw, kt) for signals (H, W, T).

        Mode-agnostic: the config's fidelity pipeline supplies every
        record-time transform —

        * ``prepare_kernels`` hooks (SLM quantization, T2 tap weights)
          run in stack order on the time-domain kernels;
        * ``shape_spectrum`` hooks build the temporal transfer function
          on the *reference's own* kt-point grid (IHB coverage, the
          recording-pulse spectrum and its compensation).  The medium is
          written before any query exists, so the recorded state must be
          a pure function of the reference — it cannot depend on the FFT
          grid of a query that arrives later; band-limiting here keeps
          the stored reference's support within kt frames, so windowed
          (overlap-save) and one-shot queries diffract off identical
          physics.
        * ``fold_gain`` hooks (echo efficiency) and the quantizer's
          per-output-channel scale are folded into the effective
          grating, diffraction being linear in the grating.

        A :class:`~repro.core.fidelity.PseudoNegative` stage is
        structural: signed kernels split into non-negative ± halves,
        both recorded, ``G⁺ − G⁻`` folded back.  An empty pipeline
        reduces exactly to the ideal FFT correlator (no prep, no
        band-limit, no encode).
        """
        cfg = self.config
        pipe = cfg.fidelity
        ker_shape = kernels.shape[-3:]
        fft_shape = spectral_conv.fft_shape_for(signal_shape, ker_shape)
        out_shape = spectral_conv.valid_shape(signal_shape, ker_shape)
        kt = int(ker_shape[-1])

        quant = pipe.get(fidelity_mod.SLMQuantize)
        pn = pipe.has(fidelity_mod.PseudoNegative)
        bits = pipe.resolved_bits(cfg.slm)
        if quant is not None:
            # shared per-output-channel quantizer range; for ± channels a
            # shared scale makes the halves subtract exactly
            scale = jnp.max(jnp.abs(kernels), axis=(1, 2, 3, 4), keepdims=True)
            scale = jnp.where(scale > 0, scale, 1.0)
        else:
            scale = jnp.ones((kernels.shape[0], 1, 1, 1, 1), kernels.dtype)
        ctx = fidelity_mod.StageContext(
            kt=kt,
            slm=cfg.slm,
            atoms=cfg.atoms,
            storage_interval_s=cfg.storage_interval_s,
            bits=bits,
            signed=not pn,
            kernel_scale=scale,
        )

        h_t = None  # None ≡ all-ones transfer: skip the band-limit FFTs
        for stage in pipe:
            h_t = stage.shape_spectrum(h_t, ctx)

        def prep(k):  # time-domain kernel transforms, in stack order
            for stage in pipe:
                k = stage.prepare_kernels(k, ctx)
            return k

        def band(k):  # temporal transfer on the reference's own grid
            if h_t is None:
                return k
            # explicit trailing-axis broadcast: (O, C, kh, kw, kt) * (kt,)
            spec = jnp.fft.fft(k, axis=-1) * h_t.reshape(
                (1,) * (k.ndim - 1) + (-1,)
            )
            return jnp.real(jnp.fft.ifft(spec, axis=-1))

        if pn:
            k_plus, k_minus = pseudo_negative.split(kernels)
            g_plus = spectral_conv.make_grating(band(prep(k_plus)), fft_shape)
            g_minus = spectral_conv.make_grating(band(prep(k_minus)), fft_shape)
            # The ± stack only feeds the unfused reference path; serving
            # configs drop it so cached gratings cost their hot-path bytes.
            keep_stacked = getattr(cfg, "keep_stacked", True)
            stacked = jnp.stack([g_plus, g_minus]) if keep_stacked else None
            # Fold the ± combine into one effective grating — static,
            # linear in the grating.
            effective = g_plus - g_minus
        else:
            stacked = None
            effective = spectral_conv.make_grating(band(prep(kernels)), fft_shape)

        if quant is not None:
            effective = effective * scale  # undo the quantizer range, once
        gain = None
        for stage in pipe:
            gain = stage.fold_gain(gain, ctx)
        if gain is not None:
            effective = effective * gain
        store = getattr(cfg, "grating_dtype", "float32")
        if store == "bfloat16":
            # Half-precision storage: split real/imag bf16 planes (complex
            # has no narrow dtype), up-cast at the MAC.  The raw ± stack
            # is an f32 validation artifact, not a serving tensor — it is
            # dropped so the grating's footprint really is half.
            eff_re = jnp.real(effective).astype(jnp.bfloat16)
            eff_im = jnp.imag(effective).astype(jnp.bfloat16)
            effective, stacked = None, None
        else:
            eff_re = eff_im = None
        return FusedGrating(
            stacked=stacked,
            effective=effective,
            fft_shape=fft_shape,
            out_shape=out_shape,
            kernel_scale=scale,
            echo_gain=jnp.asarray(1.0) if gain is None else gain,
            encode=pipe.encodes_query,
            slm_bits=bits,
            ker_shape=tuple(int(n) for n in ker_shape),
            pseudo_negative=pn,
            eff_re=eff_re,
            eff_im=eff_im,
            storage_dtype=store,
        )

    # -- query (fused hot path) --------------------------------------------

    def query(self, grating: FusedGrating, x: Array) -> Array:
        """Diffract clips x (B, C, H, W, T) off a recorded grating.

        Exactly one forward ``rfftn``, one channel-contracted MAC against
        the effective grating, one ``irfftn``.  Returns (B, O, *out_shape).

        One jitted dispatch: the program is traced once per clip shape
        and grating geometry (:attr:`query_traces` counts the traces).
        """
        return self._query_one_fn(
            x,
            grating.effective_c,
            fft_shape=grating.fft_shape,
            out_shape=grating.out_shape,
            encode=grating.encode,
            slm_bits=grating.slm_bits,
        )

    @property
    def query_traces(self) -> int:
        """How many times the one-shot query program has been traced —
        flat in steady serving, one more for each new clip shape."""
        with self._trace_lock:
            return self._query_traces

    @property
    def stream_traces(self) -> int:
        """How many times the pooled stream programs have been traced —
        on one device at most one per pool group, row bucket and
        ``n_out``, on a mesh one per pool group and row count, whatever
        the batches' compositions."""
        with self._trace_lock:
            return self._stream_traces

    def _count_stream_trace(self) -> None:
        with self._trace_lock:
            self._stream_traces += 1

    def _query_impl(self, x, effective, *, fft_shape, out_shape, encode,
                    slm_bits):
        """One-shot query body (jitted; geometry and encode static, the
        effective grating traced)."""
        with self._trace_lock:
            self._query_traces += 1
        if not encode:
            return self._query_fn()(x, effective, fft_shape, out_shape)
        enc, x_scale = self._encode(x, slm_bits)
        y = self._query_fn()(enc, effective, fft_shape, out_shape)
        # fused epilogue: only the per-example de-scaling remains — the ±
        # combine, kernel scale and echo gain were folded at record time.
        return y * x_scale

    # -- query (unfused reference) ------------------------------------------

    def query_unfused(self, grating: FusedGrating, x: Array) -> Array:
        """The seed's two-query ± path, kept as the tested/benchmarked
        reference: one ``rfftn`` + MAC + ``irfftn`` *per pseudo-negative
        grating*, digital combine and de-scaling in the epilogue.

        Pipelines without a ``PseudoNegative`` stage have nothing to
        unfuse — a single grating was recorded, so the fused path *is*
        the reference and is served directly (encoded or not)."""
        query = self._query_fn()
        if not grating.pseudo_negative:
            return self.query(grating, x)
        if grating.stacked is None:
            raise ValueError(
                "grating was recorded without the stacked ± tensors; the "
                "unfused reference path needs them"
            )
        if grating.encode:
            enc, x_scale = self._encode(x, grating.slm_bits)
        else:  # ± split without an SLM model (ablation pipelines)
            enc, x_scale = x, None
        y_plus = query(
            enc, grating.stacked[0], grating.fft_shape, grating.out_shape
        )
        y_minus = query(
            enc, grating.stacked[1], grating.fft_shape, grating.out_shape
        )
        y = pseudo_negative.combine(y_plus, y_minus)
        k_scale = grating.kernel_scale[:, 0, 0, 0, 0]  # (O,)
        y = y * k_scale[None, :, None, None, None]
        if x_scale is not None:
            y = y * x_scale
        return y * grating.echo_gain

    # -- query (streaming / overlap-save) ----------------------------------

    def query_stream(
        self,
        grating: FusedGrating,
        x: Array,
        *,
        chunk_windows: int | None = None,
        max_buffer_windows: int | None = None,
        readout_k: int | None = None,
    ) -> "Array | TopKDetections":
        """Stream clips x (B, C, H, W, T) through a window-geometry grating.

        The overlap-save driver for every streaming consumer —
        ``STHC.correlate_stream``, hybrid long-clip inference, and the
        video-search server.  The grating must have been recorded at the
        coherence-window geometry ``(H, W, block_t)``, which fixes the
        FFT grid each window rides through the fused single-FFT
        effective-grating path; the recorded physics themselves (IHB and
        pulse envelopes) live on the reference's own kt-point grid and
        are independent of this (or any) query geometry — see
        :meth:`record`.

        Per-window physical semantics: the SLM has **one** dynamic range
        for the whole stream, so encoding uses a *stream-global*
        per-example scale (max over the full clip), not one scale per
        window.  Quantization is pointwise, so encoding the stream once
        and then windowing it is exactly displaying every window at that
        shared scale — and makes streaming output equal the one-shot
        physical correlation (record-time envelopes live on the
        reference's own kt-grid, so the equality is exact to float
        tolerance; tested at the paper geometry).

        Args:
          grating: recorded at ``(H, W, block_t)``; ``block_t`` and the
            kernel shape are derived from it.
          x: (B, C, H, W, T) stream, T ≥ kt, spatial dims matching the
            record-time frame size.
          chunk_windows: windows correlated per step as one vmap'd batch
            (default: ``config.osave_chunk_windows``).
          max_buffer_windows: serve at most this many coherence windows
            from one device buffer (default:
            ``config.osave_max_buffer_windows``; None = the whole stream
            in one buffer).  Streams needing more windows are fed
            through a :class:`~repro.core.spectral_conv.StreamCursor` in
            fixed-size T-chunks with kt−1-frame carry-over tails —
            constant peak memory, output exactly equal to one-shot.
          readout_k: fuse the detection readout into the overlap-save
            epilogue: every window chunk collapses to the K best
            (score, position) pairs per (row, kernel) in-kernel, and
            only that tiny state crosses chunks and cursor segments
            (associative merge) — the stitched volume never
            materializes.  Returns a :class:`TopKDetections` whose
            ``peak_scores()`` / ``peak_index()`` equal the stitched
            volume's ``max`` / ``argmax`` bitwise.  None (default)
            returns the full correlation volume.

        Returns (B, O, H−kh+1, W−kw+1, T−kt+1), or
        :class:`TopKDetections` when ``readout_k`` is set.
        """
        if grating.ker_shape is None:
            raise ValueError(
                "grating lacks ker_shape (recorded by an older engine); "
                "re-record before streaming queries"
            )
        kh, kw, kt = grating.ker_shape
        oh, ow, ot = grating.out_shape
        frame_hw = (oh + kh - 1, ow + kw - 1)
        if tuple(x.shape[-3:-1]) != frame_hw:
            # the grating's FFT grid is baked for frame_hw at record time;
            # a different spatial size would correlate silently wrong.
            raise ValueError(
                f"clip spatial dims {tuple(x.shape[-3:-1])} do not match "
                f"the recorded frame size {frame_hw}"
            )
        plan = self.stream_plan_for(grating, x.shape[-1], chunk_windows)
        mbw = self._max_buffer_windows(max_buffer_windows)
        fused = readout_k is not None
        stream_fn = self._stream_topk_fn if fused else self._stream_fn
        static = dict(
            ker_shape=grating.ker_shape,
            fft_shape=grating.fft_shape,
            encode=grating.encode,
            slm_bits=grating.slm_bits,
        )
        if fused:
            static["k"] = int(readout_k)
        out_shape = (oh, ow, plan.n_valid)
        if mbw is None or plan.n_blocks <= mbw:
            out = stream_fn(x, grating.effective_c, plan=plan, **static)
            if fused:
                return TopKDetections(out[0], out[1], out_shape)
            return out
        # Bounded-memory chunked streaming: the stream cursor feeds the
        # same jitted driver fixed-size T-chunks with kt−1 carry-over
        # tails, so peak device residency is one segment buffer no
        # matter how long the clip.  The SLM scale stays *stream-global*
        # (computed once over the whole clip, passed into every segment)
        # — encoding is pointwise, so chunked output equals the one-shot
        # correlation exactly.  Fused readout carries only the (rows, K)
        # state across segments (local positions rebased into the
        # stream-global volume; the merge is associative, so chunked ==
        # one-shot top-K bitwise).
        cursor = spectral_conv.StreamCursor(plan, mbw)
        x_scale = _stream_scale(x) if grating.encode else None
        kt = grating.ker_shape[-1]
        outs, nv_locals, t0s = [], [], []
        for seg in cursor:
            seg_plan = spectral_conv.stream_plan(
                seg.frames, kt, plan.block_t, plan.chunk
            )
            out = stream_fn(
                x[..., seg.t0 : seg.t1],
                grating.effective_c,
                x_scale,
                plan=seg_plan,
                **static,
            )
            nv_locals.append(seg_plan.n_valid)
            t0s.append(seg.out_t0)
            outs.append(out)
        if fused:
            # rebase + merge as one jitted tail call (per-segment eager
            # ops would dominate at firehose segment counts)
            s, i = self._seg_merge_fn(
                tuple(o[0] for o in outs),
                tuple(o[1] for o in outs),
                k=int(readout_k),
                nv_locals=tuple(nv_locals),
                t0s=tuple(t0s),
                nv_total=plan.n_valid,
            )
            return TopKDetections(s, i, out_shape)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)

    def _max_buffer_windows(self, override: int | None) -> int | None:
        mbw = (
            override
            if override is not None
            else getattr(self.config, "osave_max_buffer_windows", None)
        )
        return None if mbw is None else max(int(mbw), 1)

    def stream_plan_for(
        self,
        grating: FusedGrating,
        n_frames: int,
        chunk_windows: int | None = None,
    ) -> spectral_conv.StreamPlan:
        """The overlap-save plan a streaming query of ``n_frames`` frames
        runs under — the one source of truth for window accounting
        (``query_stream`` uses it; serving metrics must report the same
        plan, derived from the grating's recorded geometry, never from a
        possibly-mutated live config)."""
        kt = grating.ker_shape[-1]
        block_t = grating.out_shape[-1] + kt - 1  # record-time window
        if chunk_windows is None:
            chunk_windows = getattr(self.config, "osave_chunk_windows", 1)
        # Pure windowing arithmetic — static ints, validated eagerly so
        # geometry errors surface outside the traced driver.
        return spectral_conv.stream_plan(n_frames, kt, block_t, chunk_windows)

    def _stream_impl(
        self,
        x,
        effective,
        x_scale=None,
        *,
        ker_shape,
        fft_shape,
        plan,
        encode,
        slm_bits,
    ):
        """Overlap-save body (jitted; shapes/plan static, arrays traced).

        ``x_scale`` carries a precomputed stream-global SLM scale when
        ``x`` is one chunk of a longer stream (the bounded-memory
        cursor); None means ``x`` is the whole stream and the scale is
        derived here."""
        kh, kw, kt = ker_shape
        H, W = x.shape[-3:-1]
        if encode:
            # stream-global SLM scale: one dynamic range per example for
            # the entire stream (see query_stream docstring).
            x, x_scale = self._encode(x, slm_bits, x_scale)
        else:
            x_scale = None
        xp = jnp.pad(x, [(0, 0)] * 4 + [(0, plan.pad_t)])
        win_out = (H - kh + 1, W - kw + 1, plan.step)
        query = self._query_fn()

        def one_window(start):
            win = lax.dynamic_slice_in_dim(xp, start, plan.block_t, axis=-1)
            return query(win, effective, fft_shape, win_out)

        starts = spectral_conv.window_starts(plan)
        # Sequential over chunks (peak memory = one chunk), batched within:
        # chunk_windows > 1 fuses that many window FFTs + spectral MACs
        # into one vmap'd batch.
        blocks = lax.map(lambda cs: jax.vmap(one_window)(cs), starts)
        y = spectral_conv.stitch_windows(blocks, plan)
        if x_scale is not None:
            # fused epilogue, as in `query`: only the per-example
            # de-scaling is left at query time.
            y = y * x_scale
        return y

    # -- query (fused detection readout) ------------------------------------

    def _readout_fn(self):
        """The per-chunk top-K reduction: the tiled Pallas readout
        kernel under ``use_pallas``, else one dense ``topk_select`` —
        identical selection math, so both paths emit bitwise-equal
        states.  Tile overrides ride ``config.readout_block_o/_l``."""
        cfg = self.config
        from repro.kernels.stmul import ops as stmul_ops  # lazy import

        use_pallas = bool(getattr(cfg, "use_pallas", False))
        tiles = dict(
            block_o=getattr(cfg, "readout_block_o", None),
            block_l=getattr(cfg, "readout_block_l", None),
        )

        def readout(vals, gidx, k):
            return stmul_ops.topk_readout(
                vals, gidx, k, use_pallas=use_pallas, **tiles
            )

        return readout

    @span("sthc.readout")
    def _chunk_topk(self, win, starts, plan, win_out, x_scale, readout, k):
        """Collapse one window chunk's correlation outputs to the
        (B, O, k) running state.

        ``win`` is (chunk, B, O, H', W', step) — the only volume-shaped
        buffer the fused path ever holds; it dies here.  Each element's
        global flat position in the C-order (H', W', n_valid) stream
        volume is synthesized from iotas (windows are disjoint spans of
        the valid time axis: t = start + t_local), pad outputs past
        ``n_valid`` are masked to −inf / the empty sentinel, and the
        de-scaling is applied *before* the reduction so scores are
        bitwise what the stitched path would have produced."""
        Hp, Wp, step = win_out
        nv = plan.n_valid
        if x_scale is not None:
            win = win * x_scale[None]  # (B,1,1,1,1) under the chunk axis
        t_glob = starts[:, None] + jax.lax.broadcasted_iota(
            jnp.int32, (plan.chunk, step), 1
        )  # (chunk, step)
        hw = jax.lax.broadcasted_iota(
            jnp.int32, (Hp, Wp), 0
        ) * Wp + jax.lax.broadcasted_iota(jnp.int32, (Hp, Wp), 1)
        gidx = hw[None, :, :, None] * nv + t_glob[:, None, None, :]
        valid = t_glob < nv  # chunk-fill windows / padded tail frames
        gidx = jnp.where(
            valid[:, None, None, :], gidx, TOPK_EMPTY_IDX
        )  # (chunk, Hp, Wp, step)
        win = jnp.where(
            valid[:, None, None, None, None, :], win, -jnp.inf
        )
        B, O = win.shape[1], win.shape[2]
        # rows and kernels folded into one (B·O) axis, padded with −inf
        # rows to whole sublane tiles *before* the chunk moves into the
        # score axis: one readout launch per chunk, no row's O padded to
        # the readout's row tile, and a relayout the TPU compiler takes
        # seconds over (minutes for a folded axis off the 8-row tile)
        n = B * O
        n_pad = -n % _SUBLANES
        win = win.reshape(win.shape[0], n, *win.shape[3:])
        if n_pad:
            win = jnp.pad(
                win, [(0, 0), (0, n_pad)] + [(0, 0)] * 3,
                constant_values=-jnp.inf,
            )
        flat = jnp.moveaxis(win, 0, 1).reshape(1, n + n_pad, -1)
        s, i = readout(flat, gidx.reshape(-1), k)
        return s[0, :n].reshape(B, O, -1), i[0, :n].reshape(B, O, -1)

    def _stream_topk_impl(
        self,
        x,
        effective,
        x_scale=None,
        *,
        ker_shape,
        fft_shape,
        plan,
        encode,
        slm_bits,
        k,
    ):
        """Fused-readout overlap-save body (jitted): the window loop of
        ``_stream_impl`` with the stitch replaced by a per-chunk top-K
        reduction.  Peak output-side memory is one chunk's windows plus
        the (n_chunks, B, O, k) states; the final cross-chunk merge is
        one more exact ``topk_select`` over those tiny states.  Returns
        (scores, index), positions local to this call's valid range."""
        kh, kw, kt = ker_shape
        H, W = x.shape[-3:-1]
        if encode:
            x, x_scale = self._encode(x, slm_bits, x_scale)
        else:
            x_scale = None
        xp = jnp.pad(x, [(0, 0)] * 4 + [(0, plan.pad_t)])
        win_out = (H - kh + 1, W - kw + 1, plan.step)
        query = self._query_fn()
        readout = self._readout_fn()

        def one_window(start):
            win = lax.dynamic_slice_in_dim(xp, start, plan.block_t, axis=-1)
            return query(win, effective, fft_shape, win_out)

        def one_chunk(cs):
            win = jax.vmap(one_window)(cs)
            return self._chunk_topk(
                win, cs, plan, win_out, x_scale, readout, k
            )

        starts = spectral_conv.window_starts(plan)
        chunk_s, chunk_i = lax.map(one_chunk, starts)  # (n_outer, B, O, k)
        return self._fold_chunk_states(chunk_s, chunk_i, k)

    @staticmethod
    def _fold_chunk_states(chunk_s, chunk_i, k):
        """(n_outer, B, O, k) per-chunk states → one exact (B, O, k)
        top-K: concatenate along the candidate axis and re-select."""
        s = jnp.moveaxis(chunk_s, 0, -2).reshape(*chunk_s.shape[1:-1], -1)
        i = jnp.moveaxis(chunk_i, 0, -2).reshape(*chunk_i.shape[1:-1], -1)
        return _merge_topk_states([(s, i)], k)

    # -- query (pooled cross-tenant batch) ----------------------------------

    def _clip_ids(self, requests, clip_keys, dedup) -> list:
        """Per-request clip identities for the dedup grouping.  Callers
        that already fingerprinted their clips (the microbatch scheduler
        hashes at submit time) pass ``clip_keys`` through; otherwise the
        bytes are digested here, memoized per array object within the
        call."""
        if not dedup:
            return [None] * len(requests)
        if clip_keys is not None:
            if len(clip_keys) != len(requests):
                raise ValueError(
                    f"clip_keys has {len(clip_keys)} entries for "
                    f"{len(requests)} requests"
                )
            return list(clip_keys)
        return clip_keys_for([x for _, x in requests])

    def query_stream_many(
        self,
        requests: "Sequence[tuple[FusedGrating, Array]]",
        *,
        chunk_windows: int | None = None,
        max_buffer_windows: int | None = None,
        clip_keys: "Sequence[tuple | None] | None" = None,
        dedup: bool = True,
        readout_k: int | None = None,
        mesh=None,
        whole_state: bool = False,
    ) -> "list[Array] | list[TopKDetections] | list[PooledTopK]":
        """Pooled :meth:`query_stream`: one overlap-save pass per group.

        Mixed-tenant clips sharing the coherence-window geometry (same
        recorded kernel/window shapes, encode semantics and stream
        length) stack on the batch axis and every window chunk runs one
        pooled FFT+MAC+IFFT against the group arena, instead of one
        overlap-save pass per tenant.  The gratings may come from
        *different* engines (mixed-fidelity serving): everything
        record-time is folded into each effective grating and the
        query-time semantics ride on the grating (``encode`` /
        ``slm_bits``), so pipelines that share them share a pool group.
        A caller that wants one pooled answer for a clip passes the clip
        as its stream: a clip of the recorded length is one window, and
        its answer equals :meth:`query` to float tolerance.

        **Clip-dedup (shared-stream fan-out).**  Within a group, requests
        whose streams hash content-equal (``clip_keys``, default computed
        via :func:`clip_key`) share one physical batch row reading the
        union of their O-slices — N tenants fanning out over one shared
        stream pay one forward FFT per window chunk, total.
        ``dedup=False`` keeps one row per request.

        Streams whose window count exceeds ``max_buffer_windows``
        (default ``config.osave_max_buffer_windows``) are fed through the
        stream cursor in fixed-size T-chunks at constant peak memory.
        Encoding stays per-example stream-global, so each request's
        output equals ``query_stream(grating_i, x_i)`` to float
        tolerance.

        **Composition is runtime data.**  Each group reads the resident
        arena of its pool group (:meth:`set_resident`; a grating not
        declared there is admitted by rebuilding the arena once).  The
        group's physical rows go to the program as single-row clips
        padded with zero rows to a row bucket (:func:`_row_bucket`), and their arena
        offsets as an int32 array; padding rows read row 0 and are
        dropped.  The program returns the whole ``(bucket, n_out, …)``
        output and each request's rows and kernels are sliced from it
        outside the program, so any mix of resident tenants runs a
        program compiled for its (bucket, ``n_out``).

        ``readout_k`` fuses the detection readout into the pooled
        epilogue (see :meth:`query_stream`): each request gets a
        :class:`TopKDetections` instead of a volume, and the pooled
        ``(B, ΣO, H', W', T')`` buffer — the serving memory ceiling at
        large tenant pools — never materializes; only (rows, K) states
        cross window chunks and cursor segments.  Bitwise equal to
        reducing the stitched volumes, dedup union-slice rows included.
        With ``whole_state`` each request gets a :class:`PooledTopK`
        instead: the group's whole state and its own rows and kernels in
        it, for a caller that copies each group's state to the host once.

        ``mesh`` — a ``(data, model)`` :class:`jax.sharding.Mesh` (see
        :func:`repro.launch.mesh.make_local_mesh`) — switches every group
        dispatch to the sharded executor.  The group's resident arena is
        shard-tiled and placed on the mesh (:meth:`_resident_arena`):
        ΣO rows over the model axis, physical stream rows over the data
        axis, the forward ``rfftn`` of each stream row running once on
        its data shard, and the MAC + fused readout shard-local
        (psum-free).  Every row computes against the whole arena
        (:func:`_fanout_layout`), so a new composition of residents
        reuses the arena and its program.  Outputs — volumes and top-K
        states, chunked-cursor and bf16 storage included — are
        bitwise-equal to the single-device path.
        """
        with span("sthc.engine.layout"):
            groups = self._group_requests(requests)
            keys = self._clip_ids(requests, clip_keys, dedup)
        results: list = [None] * len(requests)
        fused = readout_k is not None
        for idxs in groups.values():
            with span("sthc.engine.layout"):
                gratings = [requests[i][0] for i in idxs]
                g0 = gratings[0]
                if g0.ker_shape is None:
                    raise ValueError(
                        "grating lacks ker_shape (recorded by an older "
                        "engine); re-record before streaming queries"
                    )
                xs = [requests[i][1] for i in idxs]
                kh, kw, kt = g0.ker_shape
                oh, ow, _ = g0.out_shape
                frame_hw = (oh + kh - 1, ow + kw - 1)
                if tuple(xs[0].shape[-3:-1]) != frame_hw:
                    raise ValueError(
                        f"clip spatial dims {tuple(xs[0].shape[-3:-1])} do "
                        f"not match the recorded frame size {frame_hw}"
                    )
                gkeys = [keys[i] for i in idxs]
                arena = self._resident_arena(gratings, mesh)
                pool = arena.pool
                starts = [pool.o_start[arena.slot[id(g)]] for g in gratings]
                if mesh is not None:
                    # full-arena fan-out: planes live on the mesh, rows
                    # on 'model'
                    lay = _fanout_layout(starts, gkeys, int(pool.re.shape[0]))
                else:
                    lay = _span_layout(
                        starts, [g.n_out for g in gratings], gkeys,
                        pool.align, int(pool.re.shape[0]),
                    )
                pool_re, pool_im = pool.re, pool.im
                ux = [xs[j] for j in lay.uniq]
                nbs = [int(xj.shape[0]) for xj in ux]
                ub0 = [0]
                for nb in nbs:
                    ub0.append(ub0[-1] + nb)
                plan = self.stream_plan_for(g0, xs[0].shape[-1], chunk_windows)
                mbw = self._max_buffer_windows(max_buffer_windows)
                static = dict(
                    ker_shape=g0.ker_shape,
                    fft_shape=g0.fft_shape,
                    encode=g0.encode,
                    slm_bits=g0.slm_bits,
                    n_out=lay.n_out,
                    block_o=pool.block_o,
                )
                if fused:
                    static["k"] = int(readout_k)
                stream_out = (oh, ow, plan.n_valid)
            with span("sthc.engine.compose"):
                if mesh is not None:
                    fns = self._mesh_fns(mesh)
                    many_fn = fns["stream_topk"] if fused else fns["stream"]
                    # GSPMD mis-lowers a concatenate traced inside jit when
                    # its result feeds a shard_map input on a 2-axis mesh —
                    # each model shard receives the model-axis SUM of its
                    # rows — so the physical batch is packed eagerly here
                    # and the sharded drivers take exactly one array
                    batch = [ux[0] if len(ux) == 1 else jnp.concatenate(ux)]
                    n_rows = ub0[-1]
                    dsize = int(mesh.shape["data"])
                    n_pad = -(-n_rows // dsize) * dsize - n_rows
                    args = ()
                else:
                    many_fn = (
                        self._stream_many_topk_fn
                        if fused
                        else self._stream_many_fn
                    )
                    batch = [
                        xj if xj.shape[0] == 1 else xj[r : r + 1]
                        for xj in ux
                        for r in range(int(xj.shape[0]))
                    ]
                    n_rows = len(batch)
                    n_pad = _row_bucket(n_rows) - n_rows
                    rows = np.zeros((n_rows + n_pad,), np.int32)
                    rows[:n_rows] = np.repeat(lay.row_of, nbs)
                    args = (rows,)
                asked: list[dict] = [{} for _ in ux]
                for j, g in enumerate(gratings):
                    asked[lay.uniq_of[j]][id(g)] = g.n_out
                self._count_pooled(
                    sum(int(xj.shape[0]) for xj in xs), n_rows, n_pad,
                    sum(nb * sum(a.values()) for nb, a in zip(nbs, asked)),
                    n_rows * lay.n_out,
                    native=pool_re.ndim == 4,
                )
            with span("sthc.engine.dispatch"):
                if mbw is None or plan.n_blocks <= mbw:
                    out = many_fn(
                        self._pad_rows(batch, n_pad, mesh),
                        pool_re, pool_im, *args, plan=plan, **static,
                    )
                else:
                    # bounded-memory chunked pass: stream-global SLM scales
                    # measured once, then every fixed-size segment rides the
                    # same jitted pooled driver
                    cursor = spectral_conv.StreamCursor(plan, mbw)
                    x_scale = None
                    if g0.encode:
                        scales = [_stream_scale(xj) for xj in ux]
                        if mesh is None and n_pad:
                            scales.append(jnp.ones((n_pad, 1, 1, 1, 1)))
                        x_scale = (
                            scales[0]
                            if len(scales) == 1
                            else jnp.concatenate(scales, axis=0)
                        )
                    seg_outs, nv_locals, t0s = [], [], []
                    for seg in cursor:
                        seg_plan = spectral_conv.stream_plan(
                            seg.frames, kt, plan.block_t, plan.chunk
                        )
                        seg_batch = [x[..., seg.t0 : seg.t1] for x in batch]
                        seg_outs.append(many_fn(
                            self._pad_rows(seg_batch, n_pad, mesh),
                            pool_re, pool_im, *args, x_scale,
                            plan=seg_plan, **static,
                        ))
                        nv_locals.append(seg_plan.n_valid)
                        t0s.append(seg.out_t0)
                    if fused:
                        # one jitted rebase+merge tail per group: local
                        # positions land in the stream-global volume and
                        # the (rows, K) states fold, without per-segment
                        # eager dispatch overhead
                        out = self._seg_merge_fn(
                            tuple(so[0] for so in seg_outs),
                            tuple(so[1] for so in seg_outs),
                            k=int(readout_k),
                            nv_locals=tuple(nv_locals),
                            t0s=tuple(t0s),
                            nv_total=plan.n_valid,
                        )
                    else:
                        out = (
                            jnp.concatenate(seg_outs, axis=-1)
                            if len(seg_outs) > 1
                            else seg_outs[0]
                        )
            for j, i in enumerate(idxs):
                b0 = ub0[lay.uniq_of[j]]
                rsl = slice(b0, b0 + int(xs[j].shape[0]))
                ksl = slice(lay.o_off[j], lay.o_off[j] + gratings[j].n_out)
                if not fused:
                    results[i] = out[rsl, ksl]
                    continue
                part = PooledTopK(out[0], out[1], stream_out, rsl, ksl)
                results[i] = part if whole_state else part.take()
        return results

    def _pad_rows(self, batch: list, n_pad: int, mesh) -> tuple:
        """A group's row batch as the program takes it: on one device, a
        tuple of single-row device arrays (host rows start their copies
        here, in one call), padded with device-resident zero rows
        (padding uploads nothing) — every leaf a device array, so a host
        row and a padding row never make two signatures of one program;
        on a mesh, the one packed array."""
        if mesh is not None:
            return tuple(batch)
        rows = tuple(jax.device_put(batch))
        if not n_pad:
            return rows
        x = batch[0]
        key = (tuple(x.shape), jnp.dtype(x.dtype).name)
        with self._pools_lock:
            zero = self._zero_rows.get(key)
            if zero is not None:
                self._zero_rows.move_to_end(key)
        if zero is None:
            # from the host: a transfer, where jnp.zeros would compile
            zero = jax.device_put(np.zeros(x.shape, jnp.dtype(x.dtype)))
            with self._pools_lock:
                zero = self._zero_rows.setdefault(key, zero)
                while len(self._zero_rows) > self._max_zero_rows:
                    self._zero_rows.popitem(last=False)
        return rows + (zero,) * n_pad

    # -- resident arenas (the pooled stream path) ---------------------------

    def set_resident(self, gratings: "Sequence[FusedGrating]") -> None:
        """Declare the gratings the pooled stream path keeps resident.

        Each pool group (gratings sharing geometry, encode semantics,
        storage and channels) gets one arena packed from its declared
        gratings, in declaration order, on the first dispatch that needs
        it; a group whose declared gratings did not change keeps its
        arena.  The video-search server declares its tenants' gratings
        whenever a tenant is added or removed or a grating is recorded
        again.  A batch that brings an undeclared grating still runs:
        its group's arena is rebuilt with it (``arena_builds`` in
        :meth:`pool_stats` counts every packing).  A mesh keeps arenas of
        its own, under the same declaration."""
        by_key: dict[tuple, list[FusedGrating]] = {}
        seen: set[int] = set()
        for g in gratings:
            if id(g) not in seen:
                seen.add(id(g))
                by_key.setdefault(_arena_key(g), []).append(g)
        with self._pools_lock:
            self._resident = by_key
            for key, arena in list(self._arenas.items()):
                ids = tuple(id(g) for g in by_key.get(key[0], ()))
                if arena.declared != ids:
                    del self._arenas[key]

    def _resident_arena(
        self, gratings: list[FusedGrating], mesh=None
    ) -> _Arena:
        """The resident arena of the gratings' pool group on one device
        (``mesh`` None) or on ``mesh``, rebuilt when one of them is not
        in it: declared residents first, then the batch's undeclared
        gratings (so undeclared members never accumulate).

        The one place that decides an arena's layout: lane planes where
        the grouped Pallas kernel reads it, 5-D bins for the dense path.
        A mesh's arena is shard-tiled over its model axis
        (:func:`_build_pool`) and placed there once, at build time, by
        the serving rules' ``grating`` axis."""
        key = (_arena_key(gratings[0]), mesh)
        with self._pools_lock:
            arena = self._arenas.get(key)
            if arena is not None and all(id(g) in arena.slot for g in gratings):
                return arena
            declared = list(self._resident.get(key[0], ()))
            # drop the old arena before packing the new one: two arenas
            # of a large group need not be live at once
            self._arenas.pop(key, None)
        ids = {id(g) for g in declared}
        extra, _ = _dedup_members([g for g in gratings if id(g) not in ids])
        members = declared + extra
        align, block_o = self._slot_layout(members)
        pool = _build_pool(
            members, align,
            shards=1 if mesh is None else int(mesh.shape["model"]),
            lanes=bool(getattr(self.config, "use_pallas", False)),
            block_o=block_o,
        )
        if mesh is not None:
            from repro.distributed import sharding as shardlib  # lazy

            spec = shardlib.spec_for(
                pool.re.shape,
                ("grating",) + (None,) * (pool.re.ndim - 1),
                shardlib.make_serving_rules(),
                mesh,
            )
            placed = jax.sharding.NamedSharding(mesh, spec)
            pool = dataclasses.replace(
                pool,
                re=jax.device_put(pool.re, placed),
                im=jax.device_put(pool.im, placed),
            )
        arena = _Arena(
            pool=pool,
            slot={id(g): i for i, g in enumerate(members)},
            declared=tuple(id(g) for g in declared),
        )
        with self._pools_lock:
            self._arenas[key] = arena
            self._arena_builds += 1
        return arena

    def _group_requests(self, requests) -> dict:
        """Pool-group the requests: same FFT geometry + encode semantics
        + storage dtype + clip geometry can share one arena/dispatch."""
        groups: dict[tuple, list[int]] = {}
        for i, (g, x) in enumerate(requests):
            if x.ndim != 5:
                raise ValueError(
                    f"request {i}: clips must be (B, C, H, W, T), got "
                    f"shape {tuple(x.shape)}"
                )
            if int(x.shape[1]) != g.channels:
                raise ValueError(
                    f"request {i}: clip has {x.shape[1]} channels; the "
                    f"grating was recorded with {g.channels}"
                )
            key = (
                g.fft_shape,
                g.out_shape,
                g.ker_shape,
                bool(g.encode),
                int(g.slm_bits) if g.encode else -1,
                g.storage_dtype,
                tuple(x.shape[1:]),
                str(x.dtype),
            )
            groups.setdefault(key, []).append(i)
        return groups

    def _slot_layout(
        self, members: "Sequence[FusedGrating]"
    ) -> tuple[int, int | None]:
        """``(align, block_o)`` of a pool group's arena: the rows from
        one member slot's start to the next, and the grouped Pallas
        kernel's O block.  Where every member has the same kernel count
        O, the stride is O, so the arena holds no zero rows, and the
        block is O or, above ``MAX_BLOCK_O`` rows, its largest divisor
        that fits (:func:`~repro.kernels.stmul.kernel.grouped_block_o`).
        Members of different counts sit on the kernel's ``BLOCK_O``
        grid, so one declaration of another count moves the whole
        group to that grid (docs/serving.md).  An explicit
        ``stmul_block_o`` sets the stride and the block alike.  The
        dense gather path needs no alignment and no block."""
        cfg = self.config
        if not getattr(cfg, "use_pallas", False):
            return 1, None
        explicit = getattr(cfg, "stmul_block_o", None)
        if explicit:
            return int(explicit), int(explicit)
        from repro.kernels.stmul import kernel as stmul_kernel  # lazy

        counts = {int(g.n_out) for g in members}
        if len(counts) == 1:
            (o,) = counts
            return o, stmul_kernel.grouped_block_o(o)
        return stmul_kernel.BLOCK_O, stmul_kernel.BLOCK_O

    # -- mesh-sharded execution (query_stream_many mesh=) ------------------

    def _mesh_fns(self, mesh) -> dict:
        """Per-mesh jitted sharded drivers, memoized (the Mesh is
        hashable and long-lived — a server builds one per replica)."""
        with self._pools_lock:
            fns = self._mesh_jits.get(mesh)
        if fns is not None:
            return fns
        fns = self._make_mesh_fns(mesh)
        with self._pools_lock:
            fns = self._mesh_jits.setdefault(mesh, fns)
        return fns

    def _make_mesh_fns(self, mesh) -> dict:
        """Build the sharded pooled drivers for one ``(data, model)``
        mesh: the single-device pooled overlap-save bodies wrapped in
        ``shard_map``, stream rows on the data axis, arena rows on the
        model axis.

        Bitwise equality with the single-device path holds by
        construction: the shard body reuses ``_pooled_osave_setup`` /
        ``_chunk_topk`` / ``_fold_chunk_states`` verbatim with all-zero
        row offsets over its local arena tile, so every
        (clip row, kernel row) element runs the exact op sequence —
        encode, one ``rfftn`` per stream row, the batched-sel MAC (or
        grouped Pallas launch), ``irfftn``, stitch or fused top-K — the
        unsharded driver runs; sharding only partitions the loop, it
        reorders no reduction.  ``check_vma=False`` because
        ``pallas_call`` has no varying-manual-axes rule; the bodies
        are collective-free (each tenant's O-slice lives on exactly one
        model shard, so no psum is ever needed)."""
        from jax.sharding import PartitionSpec as P  # lazy
        from repro.distributed import sharding as shardlib  # lazy

        if "data" not in mesh.shape or "model" not in mesh.shape:
            raise ValueError(
                "mesh must carry ('data', 'model') axes (see "
                f"launch.mesh.make_local_mesh); got {dict(mesh.shape)}"
            )
        rules = shardlib.make_serving_rules()
        dsize = int(mesh.shape["data"])
        msize = int(mesh.shape["model"])

        def specs_for(x, pool_re):
            xspec = shardlib.spec_for(
                x.shape, ("stream_batch",) + (None,) * (x.ndim - 1),
                rules, mesh,
            )
            gspec = shardlib.spec_for(
                pool_re.shape, ("grating",) + (None,) * (pool_re.ndim - 1),
                rules, mesh,
            )
            return xspec, gspec

        def pad_b(x, x_scale):
            """Zero-pad stream rows up to the data-axis size: pad rows
            cost compute on their shard and are dropped with the rest of
            the whole output (scale pads to 1 — encode of an all-zero
            row divides by the same 1.0 the derived scale would use)."""
            b = int(x.shape[0])
            b_pad = -(-b // dsize) * dsize
            if b_pad > b:
                x = jnp.pad(x, [(0, b_pad - b)] + [(0, 0)] * (x.ndim - 1))
                if x_scale is not None:
                    x_scale = jnp.pad(
                        x_scale,
                        [(0, b_pad - b)] + [(0, 0)] * (x_scale.ndim - 1),
                        constant_values=1.0,
                    )
            return x, x_scale

        def run(body, x, pool_re, pool_im, x_scale, out_specs):
            xspec, gspec = specs_for(x, pool_re)
            if x_scale is None:
                f = jax.shard_map(
                    lambda xl, prl, pil: body(xl, prl, pil, None),
                    mesh=mesh,
                    in_specs=(xspec, gspec, gspec),
                    out_specs=out_specs,
                    check_vma=False,
                )
                return f(x, pool_re, pool_im)
            f = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(xspec, gspec, gspec, xspec),
                out_specs=out_specs,
                check_vma=False,
            )
            return f(x, pool_re, pool_im, x_scale)

        def stream_many(
            xs, pool_re, pool_im, x_scale=None, *,
            ker_shape, fft_shape, plan, encode, slm_bits, n_out, block_o,
        ):
            # full-arena fan-out: every row reads the whole local tile
            # (zero offsets); `n_out` is the whole arena's row count
            self._count_stream_trace()
            if len(xs) != 1:
                raise ValueError(
                    "sharded stream drivers take one pre-packed batch "
                    "(an in-jit concatenate feeding shard_map "
                    "mis-reshards on 2-axis meshes; the caller "
                    "concatenates eagerly)"
                )
            x, x_scale = pad_b(xs[0], x_scale)
            b_local = int(x.shape[0]) // dsize
            s_local = int(n_out) // msize

            def body(xl, prl, pil, xsl):
                one_window, _, xs_l = self._pooled_osave_setup(
                    (xl,), prl, pil, jnp.zeros((b_local,), jnp.int32), xsl,
                    ker_shape=ker_shape,
                    fft_shape=fft_shape, plan=plan, encode=encode,
                    slm_bits=slm_bits, n_out=s_local, block_o=block_o,
                )
                starts = spectral_conv.window_starts(plan)
                blocks = lax.map(
                    lambda cs: jax.vmap(one_window)(cs), starts
                )
                y = spectral_conv.stitch_windows(blocks, plan)
                if xs_l is not None:
                    y = y * xs_l
                return y

            return run(body, x, pool_re, pool_im, x_scale, P("data", "model"))

        def stream_many_topk(
            xs, pool_re, pool_im, x_scale=None, *,
            ker_shape, fft_shape, plan, encode, slm_bits, n_out, block_o, k,
        ):
            self._count_stream_trace()
            if len(xs) != 1:
                raise ValueError(
                    "sharded stream drivers take one pre-packed batch "
                    "(an in-jit concatenate feeding shard_map "
                    "mis-reshards on 2-axis meshes; the caller "
                    "concatenates eagerly)"
                )
            x, x_scale = pad_b(xs[0], x_scale)
            b_local = int(x.shape[0]) // dsize
            s_local = int(n_out) // msize
            readout = self._readout_fn()

            def body(xl, prl, pil, xsl):
                one_window, win_out, xs_l = self._pooled_osave_setup(
                    (xl,), prl, pil, jnp.zeros((b_local,), jnp.int32), xsl,
                    ker_shape=ker_shape,
                    fft_shape=fft_shape, plan=plan, encode=encode,
                    slm_bits=slm_bits, n_out=s_local, block_o=block_o,
                )

                def one_chunk(cs):
                    win = jax.vmap(one_window)(cs)
                    return self._chunk_topk(
                        win, cs, plan, win_out, xs_l, readout, k
                    )

                starts = spectral_conv.window_starts(plan)
                chunk_s, chunk_i = lax.map(one_chunk, starts)
                return self._fold_chunk_states(chunk_s, chunk_i, k)

            spec = P("data", "model")
            return run(body, x, pool_re, pool_im, x_scale, (spec, spec))

        return {
            "stream": jax.jit(
                stream_many,
                static_argnames=(
                    "ker_shape", "fft_shape", "plan", "encode", "slm_bits",
                    "n_out", "block_o",
                ),
            ),
            "stream_topk": jax.jit(
                stream_many_topk,
                static_argnames=(
                    "ker_shape", "fft_shape", "plan", "encode", "slm_bits",
                    "n_out", "block_o", "k",
                ),
            ),
        }

    def _stream_many_impl(
        self, xs, pool_re, pool_im, rows, x_scale=None,
        *, ker_shape, fft_shape, plan, encode, slm_bits, n_out, block_o,
    ):
        """Pooled overlap-save body (jitted; mirrors ``_stream_impl``).

        ``xs`` is the tuple of single-row clips of the group's physical
        rows, padded to the row bucket (stacked in-trace so the eager
        path dispatches nothing; clip-dedup means one row may serve
        several requests); ``rows`` the int32 per-row arena offsets.
        ``x_scale`` carries precomputed stream-global SLM scales when
        the clips are cursor segments of longer streams.  Returns the
        whole ``(rows, n_out, H', W', T')`` volume; callers slice each
        request's rows and O-window from it.  ``block_o`` is the grouped
        kernel's O block (``GratingPool.block_o``)."""
        self._count_stream_trace()
        one_window, win_out, x_scale = self._pooled_osave_setup(
            xs, pool_re, pool_im, rows, x_scale,
            ker_shape=ker_shape, fft_shape=fft_shape, plan=plan,
            encode=encode, slm_bits=slm_bits, n_out=n_out, block_o=block_o,
        )
        starts = spectral_conv.window_starts(plan)
        blocks = lax.map(lambda cs: jax.vmap(one_window)(cs), starts)
        y = spectral_conv.stitch_windows(blocks, plan)
        if x_scale is not None:
            y = y * x_scale
        return y

    def _pooled_osave_setup(
        self, xs, pool_re, pool_im, rows, x_scale,
        *, ker_shape, fft_shape, plan, encode, slm_bits, n_out, block_o,
    ):
        """Shared front half of the pooled overlap-save bodies: stack
        the per-row clips, encode (stream-global scale), pad the time
        axis and build the per-window pooled query closure (grouped
        Pallas launch on lane planes under ``use_pallas``, hoisted-gather
        einsum otherwise) reading arena rows ``[rows[b], rows[b] +
        n_out)``.
        Returns (one_window, win_out, x_scale)."""
        x = xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
        rows = jnp.asarray(rows, jnp.int32)
        kh, kw, kt = ker_shape
        H, W = x.shape[-3:-1]
        if encode:
            x, x_scale = self._encode(x, slm_bits, x_scale)
        else:
            x_scale = None
        xp = jnp.pad(x, [(0, 0)] * 4 + [(0, plan.pad_t)])
        win_out = (H - kh + 1, W - kw + 1, plan.step)
        if getattr(self.config, "use_pallas", False):
            query = self._pooled_query_fn(block_o)

            def one_window(start):
                win = lax.dynamic_slice_in_dim(
                    xp, start, plan.block_t, axis=-1
                )
                return query(
                    win, pool_re, pool_im, rows, n_out, fft_shape, win_out
                )

        else:
            # dense path: the per-row arena gather is window-independent
            # — hoist it out of the overlap-save loop so each window pays
            # only the FFT+MAC+IFFT, not a fresh pool materialization
            sel = _pool_select(pool_re, pool_im, rows, n_out)

            def one_window(start):
                win = lax.dynamic_slice_in_dim(
                    xp, start, plan.block_t, axis=-1
                )
                return _presel_query_dense(win, sel, fft_shape, win_out)

        return one_window, win_out, x_scale

    def _stream_many_topk_impl(
        self, xs, pool_re, pool_im, rows, x_scale=None,
        *, ker_shape, fft_shape, plan, encode, slm_bits, n_out, block_o, k,
    ):
        """Fused-readout pooled overlap-save body (jitted): the window
        loop of ``_stream_many_impl`` with the stitch replaced by the
        per-chunk top-K reduction — the pooled ``(B, n_out, H', W', T')``
        volume (the serving memory ceiling at large tenant pools) never
        materializes.  Per-request slicing commutes with the per-(row,
        kernel) reduction, so dedup union-span states split exactly like
        volumes.  Returns the group's whole (scores, index) state, each
        ``(rows, n_out, k)``, positions local to this call's valid
        range."""
        self._count_stream_trace()
        one_window, win_out, x_scale = self._pooled_osave_setup(
            xs, pool_re, pool_im, rows, x_scale,
            ker_shape=ker_shape, fft_shape=fft_shape, plan=plan,
            encode=encode, slm_bits=slm_bits, n_out=n_out, block_o=block_o,
        )
        readout = self._readout_fn()

        def one_chunk(cs):
            win = jax.vmap(one_window)(cs)
            return self._chunk_topk(
                win, cs, plan, win_out, x_scale, readout, k
            )

        starts = spectral_conv.window_starts(plan)
        chunk_s, chunk_i = lax.map(one_chunk, starts)
        return self._fold_chunk_states(chunk_s, chunk_i, k)

    def _pooled_query_fn(self, block_o: int):
        """The per-window pooled FFT+MAC+IFFT of the Pallas path: the
        grouped stmul launch on a lane-plane arena, in O blocks of
        ``block_o`` rows (the arena's ``GratingPool.block_o``)."""
        from repro.kernels.stmul import ops as stmul_ops  # lazy import

        tiles = dict(
            block_o=block_o,
            block_f=getattr(self.config, "stmul_block_f", None),
        )

        def query(x, pool_re, pool_im, rows, n_out, fft_shape, out_shape):
            return stmul_ops.query_grating_pooled(
                x, pool_re, pool_im, rows, n_out, fft_shape, out_shape,
                **tiles,
            )

        return query

    # -- internals ---------------------------------------------------------

    @span("sthc.encode")
    def _encode(
        self, x: Array, bits: int, x_scale: Array | None = None
    ) -> tuple[Array, Array]:
        """SLM front end: non-negative clip, one scale per *example* — the
        channel sum at the detector means a per-channel scale could not
        be undone digitally.  ``bits`` is the grating's record-time
        resolved depth (pipeline stage override or SLM config).
        ``x_scale`` overrides the derived scale when ``x`` is one chunk
        of a longer stream whose global dynamic range was measured
        upfront.  Returns (encoded, x_scale)."""
        x = jnp.maximum(x, 0.0)
        if x_scale is None:
            x_scale = jnp.max(x, axis=(1, 2, 3, 4), keepdims=True)  # (B,1,...)
            x_scale = jnp.where(x_scale > 0, x_scale, 1.0)
        return optics.quantize_unit(x / x_scale, bits), x_scale

    def _query_fn(self):
        cfg = self.config
        if not getattr(cfg, "use_pallas", False):
            return spectral_conv.query_grating
        from repro.kernels.stmul import ops as stmul_ops  # lazy import

        version = getattr(cfg, "stmul_version", 2)
        min_mxu_c = getattr(cfg, "stmul_min_mxu_c", None)
        tiles = dict(
            block_b=getattr(cfg, "stmul_block_b", None),
            block_o=getattr(cfg, "stmul_block_o", None),
            block_f=getattr(cfg, "stmul_block_f", None),
        )

        def query(x, grating, fft_shape, out_shape):
            return stmul_ops.query_grating_pallas(
                x,
                grating,
                fft_shape,
                out_shape,
                version=version,
                min_mxu_c=min_mxu_c,
                **tiles,
            )

        return query


# ---------------------------------------------------------------------------
# Grating cache — record once across calls, not just inside one call
# ---------------------------------------------------------------------------


def _grating_checksum(grating: FusedGrating) -> float:
    """Content checksum of a recorded grating: Σ|re| + Σ|im| over the
    stored planes, accumulated in f32.  One device reduction + host
    sync; NaN poisoning or bit rot moves (or NaNs) the sum, and the
    NaN-safe comparison in ``GratingCache`` treats NaN as a mismatch."""
    re, im = grating.planes
    total = jnp.sum(jnp.abs(re.astype(jnp.float32))) + jnp.sum(
        jnp.abs(im.astype(jnp.float32))
    )
    return float(total)


class _InFlight:
    """Per-key record-in-progress marker: waiters block on ``event`` and
    pick up ``grating`` even when the result was not admitted to the
    cache (oversized / tenant discarded), so a cold key never records
    more than once per concurrent burst."""

    __slots__ = ("event", "grating")

    def __init__(self):
        self.event = threading.Event()
        self.grating: FusedGrating | None = None


class GratingCache:
    """Content-addressed LRU cache of recorded gratings.

    Keyed on the kernel *bytes* (SHA-1), kernel shape/dtype, the signal
    shape (which fixes the FFT grid) and the *record-relevant* subset of
    ``STHCConfig`` — the fidelity pipeline's stable fingerprint plus the
    device configs it reads (SLM, atoms, storage interval).  The
    fingerprint is what lets one shared cache serve tenants at
    different fidelities: same kernels under two pipelines occupy two
    entries, and a lookup can never cross-hit another fidelity's
    grating.  Query-side knobs (``use_pallas``, ``stmul_version``,
    ``fused``, ``osave_chunk_windows``, …) deliberately do not key:
    they don't change what was written into the medium, and splitting
    on them would re-record physically identical gratings.  Inside
    ``jit`` the kernels are tracers with no bytes to hash; those calls
    bypass the cache (the grating computation is traced inline, exactly
    as before).

    The LRU budget is two-dimensional: ``max_entries`` recorded kernel
    sets *and* (optionally) ``max_bytes`` of grating storage — the
    multi-tenant serving knobs.  Least-recently-used entries are evicted
    until both budgets hold; a single grating larger than ``max_bytes``
    is never admitted at all (the cache cannot hold it, so it is served
    uncached rather than flushing every resident peer).  Counters
    (``hits`` / ``misses`` / ``evictions``) and the live byte footprint
    are exposed via :meth:`stats` for the serving metrics.
    """

    def __init__(
        self,
        max_entries: int = 8,
        max_bytes: int | None = None,
        verify: bool = False,
    ):
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        # verify: checksum every hit against the sum recorded at
        # insertion; a mismatch (bit rot / NaN corruption / raced
        # mutation) discards the entry and the fetch falls through to a
        # fresh record — a self-healing cache.  Off by default: each
        # verified hit costs one device reduction + host sync.
        self.verify = verify
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        self.evictions = 0  # guarded-by: _lock
        self.shared = 0  # in-flight results never admitted; guarded-by: _lock
        self.integrity_failures = 0  # verify=True mismatches; guarded-by: _lock
        self._entries: OrderedDict[tuple, FusedGrating] = OrderedDict()  # guarded-by: _lock
        self._sums: dict[tuple, float] = {}  # insertion checksums; guarded-by: _lock
        self._nbytes = 0  # guarded-by: _lock
        self._lock = threading.Lock()
        # per-key in-flight record markers: concurrent misses for one key
        # wait on the first recorder instead of each re-running the
        # expensive device-side record (thundering herd on a cold tenant)
        self._inflight: dict[tuple, _InFlight] = {}  # guarded-by: _lock

    @staticmethod
    def key_for(
        kernels: Array, signal_shape: tuple[int, int, int], config
    ) -> tuple | None:
        """Cache key, or None when kernels are abstract (under tracing)."""
        if isinstance(kernels, jax.core.Tracer):
            return None
        arr = np.asarray(kernels)
        digest = hashlib.sha1(arr.tobytes()).hexdigest()
        store = getattr(config, "grating_dtype", "float32")
        record_cfg = (
            config.fidelity.fingerprint(),
            config.slm,
            config.atoms,
            config.storage_interval_s,
            # record-side: changes what object is stored (± stack or not),
            # so stripped serving gratings never alias full ones — but
            # only when the pipeline splits ± channels at all; other
            # gratings have no stack (bf16 storage always drops it), and
            # splitting on the knob would double-record identical ones.
            (
                getattr(config, "keep_stacked", True)
                if config.fidelity.has(fidelity_mod.PseudoNegative)
                and store == "float32"
                else True
            ),
            # storage precision changes the stored object (and its
            # numerics), so bf16 and f32 gratings never alias
            store,
        )
        return (digest, arr.shape, str(arr.dtype), tuple(signal_shape), record_cfg)

    def get_or_record(
        self,
        engine: QueryEngine,
        kernels: Array,
        signal_shape: tuple[int, int, int],
        key: tuple | None = None,
        admit=None,
    ) -> FusedGrating:
        """Fetch the grating for ``kernels``, recording on a miss.

        ``key`` lets long-lived callers (the video-search server) hash
        the kernel bytes once at registration instead of on every query;
        when omitted it is derived here via :meth:`key_for`.

        ``admit`` (optional, ``() -> bool``) is consulted under the cache
        lock just before a freshly-recorded grating is inserted: when it
        returns False the grating is served uncached and no resident
        peer is evicted to make room for it — the server uses this so a
        record in flight for a just-removed tenant cannot flush live
        entries.  The callback must not acquire locks ordered before
        this cache's.
        """
        if key is None:
            key = self.key_for(kernels, signal_shape, engine.config)
        if key is None:  # tracer kernels: nothing to address by
            return engine.record(kernels, signal_shape)
        while True:
            with self._lock:
                hit = self._entries.get(key)
                expect = self._sums.get(key)
                if hit is not None and not self.verify:
                    self.hits += 1
                    self._entries.move_to_end(key)
                    return hit
                pending = None
                if hit is None:
                    pending = self._inflight.get(key)
                    if pending is None:
                        self._inflight[key] = pending = _InFlight()
                        break  # this thread records
            if hit is not None:
                # verify outside the lock: the checksum is a device
                # reduction + host sync, far too slow to serialize peers
                if self._checksum_ok(hit, expect):
                    with self._lock:
                        if self._entries.get(key) is hit:
                            self.hits += 1
                            self._entries.move_to_end(key)
                    return hit
                # corrupted in residence: drop the entry and loop back
                # to a fresh record — a self-healing fetch
                with self._lock:
                    if self._entries.get(key) is hit:
                        self._entries.pop(key)
                        self._sums.pop(key, None)
                        self._nbytes -= hit.nbytes
                        self.integrity_failures += 1
                continue
            # another thread is recording this key: wait, then either
            # take the cached entry (re-check above), share the
            # recorder's result even when it wasn't admitted (oversized /
            # discarded — identical content, no point re-recording), or
            # become the recorder ourselves if it raised.
            pending.event.wait()
            if pending.grating is not None:
                with self._lock:
                    if key in self._entries:
                        self.hits += 1
                        self._entries.move_to_end(key)
                    else:
                        # shared from the recorder but never admitted
                        # (oversized / discarded): don't inflate the hit
                        # rate the byte-budget stats exist to diagnose
                        self.shared += 1
                return pending.grating
        try:
            grating = engine.record(kernels, signal_shape)
            # checksum before taking the lock (device reduction); only
            # needed when hits will verify against it
            chk = _grating_checksum(grating) if self.verify else None
            pending.grating = grating  # share with waiters even if not admitted
            with self._lock:
                self.misses += 1
                if admit is not None and not admit():
                    return grating  # caller lost interest mid-record
                if (
                    self.max_bytes is not None
                    and grating.nbytes > self.max_bytes
                ):
                    # larger than the whole byte budget: the cache cannot
                    # hold it — serve it uncached instead of flushing
                    # every resident peer trying to make room that cannot
                    # exist.
                    return grating
                if key in self._entries:  # raced with another recorder
                    self._nbytes -= self._entries.pop(key).nbytes
                    self._sums.pop(key, None)
                self._entries[key] = grating
                if chk is not None:
                    self._sums[key] = chk
                self._nbytes += grating.nbytes
                while self._entries and self._over_budget():
                    evicted_key, evicted = self._entries.popitem(last=False)
                    self._sums.pop(evicted_key, None)
                    self._nbytes -= evicted.nbytes
                    self.evictions += 1
        finally:
            with self._lock:
                self._inflight.pop(key, None)
            pending.event.set()
        return grating

    @staticmethod
    def _checksum_ok(grating: FusedGrating, expect: float | None) -> bool:
        """NaN-safe checksum comparison: a NaN fresh sum (poisoned
        planes) must read as a mismatch, so compare with ``<=`` rather
        than ``!=``.  ``expect`` is None for entries inserted before
        verification was enabled — nothing to compare against."""
        if expect is None:
            return True
        fresh = _grating_checksum(grating)
        return abs(fresh - expect) <= 1e-3 * max(abs(expect), 1.0)

    def peek(self, key: tuple | None) -> FusedGrating | None:
        """The resident grating under ``key``, or None; records nothing
        and moves no counter or LRU position."""
        if key is None:
            return None
        with self._lock:
            return self._entries.get(key)

    def discard(self, key: tuple | None) -> bool:
        """Explicitly invalidate one entry (tenant removal) — frees its
        bytes without touching the eviction counter or any peer."""
        if key is None:
            return False
        with self._lock:
            grating = self._entries.pop(key, None)
            if grating is None:
                return False
            self._sums.pop(key, None)
            self._nbytes -= grating.nbytes
            return True

    def _over_budget(self) -> bool:
        if len(self._entries) > self.max_entries:
            return True
        return self.max_bytes is not None and self._nbytes > self.max_bytes

    @property
    def nbytes(self) -> int:
        """Current grating storage held by the cache, in bytes."""
        return self._nbytes

    def stats(self) -> dict:
        """Counter/footprint snapshot for serving metrics dashboards."""
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "shared": self.shared,
                "entries": len(self._entries),
                "bytes": self._nbytes,
                "max_entries": self.max_entries,
                "max_bytes": self.max_bytes,
                "verify": self.verify,
                "integrity_failures": self.integrity_failures,
            }

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._sums.clear()
            self._nbytes = 0
            self.hits = 0
            self.misses = 0
            self.evictions = 0
            self.shared = 0
            self.integrity_failures = 0


_DEFAULT_CACHE = GratingCache()


def default_cache() -> GratingCache:
    """Process-wide grating cache shared by STHC / hybrid / serving."""
    return _DEFAULT_CACHE
