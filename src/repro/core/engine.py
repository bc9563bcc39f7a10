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

* **Pooled serving** — ``query_many`` / ``query_stream_many`` extend the
  weight-stationary dataflow *across tenants*: resident effective
  gratings that share FFT geometry and encode semantics are packed into
  one ``(ΣO, C, FH, FW, FTr)`` arena (:class:`GratingPool`, memoized
  while its members live) and a mixed-tenant clip batch is answered with
  exactly one forward FFT, one pooled channel-contracted MAC in which
  every clip row reads only its own tenant's O-offset slice, and one
  inverse FFT — N same-geometry tenants pay 1 device dispatch instead of
  N.  **Clip-dedup** takes the fan-out the rest of the way to the
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
        tiles to f32 — f32 accumulation either way).
      o_start: per-member first-row offset.  Member slots are padded to
        ``align`` rows (the Pallas grouped kernel indexes the arena in
        O-tile units; the dense gather path uses align=1), and the arena
        carries enough tail rows that every ``o_start[i] + n_out`` read
        stays in bounds.
      n_out: rows each pooled query reads/writes per request (the widest
        member slot); per-request outputs are cropped back to their own
        O.
      members: strong references to the member gratings — the arena is a
        pure repack of their planes, and pinning them keeps the
        identity-keyed pool cache sound.
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
        first row).
      o_off: per group-local request — offset of its tenant's O-slice
        inside its physical row's span.
      n_out: rows every physical row reads/writes (the widest span,
        aligned to the pool's O-tile grid).
    """

    uniq: list[int]
    uniq_of: list[int]
    row_of: list[int]
    o_off: list[int]
    n_out: int


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
    order, ties broken by lowest tile index — deterministic, so the
    identity-keyed pool cache stays sound) and the per-tile row count
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
    members: list[FusedGrating], align: int, shards: int = 1
) -> GratingPool:
    """Pack member gratings' planes into one arena (see GratingPool).

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
        o_start = []
        row = 0
        for i in range(len(members)):
            re, im = padded(i)
            res.append(re)
            ims.append(im)
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
    )


def clip_key(x) -> tuple | None:
    """Content fingerprint of a clip batch — the shared-stream identity.

    Two requests whose clips hash equal (bytes + shape + dtype) are the
    *same stream*: the pooled executor answers them with one forward FFT
    over one physical copy, each tenant reading its own O-slice of the
    union span (see :meth:`QueryEngine.query_many`).  Hashing is the
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


def _pad_arena(
    pool_re: Array, pool_im: Array, max_row: int, n_out: int
) -> tuple[Array, Array]:
    """Zero-pad arena rows so every ``[row, row + n_out)`` read stays in
    bounds.  The pool's own tail covers per-member slot reads; union
    spans (clip-dedup) can read wider than any single slot, and jnp
    fancy-indexing would clamp out-of-bounds rows to the last member
    silently."""
    need = int(max_row) + int(n_out) - int(pool_re.shape[0])
    if need <= 0:
        return pool_re, pool_im
    widths = [(0, need)] + [(0, 0)] * (pool_re.ndim - 1)
    return jnp.pad(pool_re, widths), jnp.pad(pool_im, widths)


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
    yhat = jnp.einsum("bcxyz,bocxyz->boxyz", xhat, sel, precision="highest")
    return spectral_conv.irfft3(yhat, fft_shape, out_shape)


def _pooled_query_dense(
    x: Array,
    pool_re: Array,
    pool_im: Array,
    rows: Array,
    n_out: int,
    fft_shape: tuple[int, int, int],
    out_shape: tuple[int, int, int],
) -> Array:
    """Dense pooled query: offset-gather + einsum."""
    sel = _pool_select(pool_re, pool_im, rows, n_out)
    return _presel_query_dense(x, sel, fft_shape, out_shape)


# ---------------------------------------------------------------------------
# Fused detection readout — the streaming top-K state
# ---------------------------------------------------------------------------

# Sentinel for an unfilled/poisoned top-K slot (int32 max, matching
# kernels.stmul.kernel.TOPK_EMPTY_IDX without importing Pallas eagerly).
TOPK_EMPTY_IDX = 2**31 - 1


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

    _max_pools = 8  # LRU bound on memoized cross-tenant arenas

    def __init__(self, config: "STHCConfig"):
        self.config = config
        # jitted overlap-save driver; built eagerly (wrapper creation is
        # free, tracing happens on first call) so concurrent first
        # queries from server threads can't race a lazy init
        self._stream_fn = jax.jit(
            self._stream_impl,
            static_argnames=(
                "ker_shape", "fft_shape", "plan", "encode", "slm_bits",
            ),
        )
        # pooled streaming driver + the cross-tenant arena cache.  The
        # request composition (per-row offsets, per-request splits) is
        # *static*: steady-state serving compositions repeat call after
        # call, and baking them into the trace removes every eager
        # per-request op (host→device offset transfers, result slicing)
        # from the hot path — the pooled dispatch is exactly one jitted
        # call.  The flip side is a retrace per *novel* composition, so
        # callers should canonicalize request order (the server sorts
        # its tenant groups) to keep the composition space small.
        self._stream_many_fn = jax.jit(
            self._stream_many_impl,
            static_argnames=(
                "rows", "splits", "ker_shape", "fft_shape", "plan",
                "encode", "slm_bits", "n_out",
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
                "rows", "splits", "ker_shape", "fft_shape", "plan",
                "encode", "slm_bits", "n_out", "k",
            ),
        )
        # cross-segment state tail (rebase + merge) as one launch — the
        # cursor path's per-request epilogue
        self._seg_merge_fn = jax.jit(
            _segments_rebase_merge,
            static_argnames=("k", "nv_locals", "t0s", "nv_total"),
        )
        self._pools: OrderedDict[tuple, GratingPool] = OrderedDict()  # guarded-by: _pools_lock
        # row-padded arena views for dedup union spans that overhang the
        # pool tail: keyed (pool, rows needed) so steady-state mixed-span
        # compositions reuse one padded device buffer instead of paying
        # an O(arena) jnp.pad per dispatch.  Entries hold the pool
        # (strong ref: id-keyed lookups stay sound) + the padded planes.
        self._padded: OrderedDict[tuple, tuple] = OrderedDict()  # guarded-by: _pools_lock
        # mesh serving state: per-Mesh jitted sharded drivers and
        # per-(pool, mesh) arena placements (planes device_put once with
        # rows NamedSharding'd over the model axis, reused across
        # dispatches).  A server owns one mesh per replica, so both
        # caches stay tiny.
        self._mesh_jits: dict = {}  # guarded-by: _pools_lock
        self._mesh_arenas: OrderedDict[tuple, tuple] = OrderedDict()  # guarded-by: _pools_lock
        self._pools_lock = threading.Lock()
        # shared-stream fan-out accounting (clip-dedup in the pooled
        # paths): offered = clip rows requested, dispatched = physical
        # rows after collapsing same-content clips onto shared rows.
        self._pooled_dispatches = 0  # guarded-by: _pools_lock
        self._pooled_rows_offered = 0  # guarded-by: _pools_lock
        self._pooled_rows_dispatched = 0  # guarded-by: _pools_lock

    def pool_stats(self) -> dict:
        """Pooled-executor counters for serving metrics: how many clip
        rows the dedup collapsed (``rows_saved``) out of those offered."""
        with self._pools_lock:
            offered = self._pooled_rows_offered
            dispatched = self._pooled_rows_dispatched
            return {
                "dispatches": self._pooled_dispatches,
                "rows_offered": offered,
                "rows_dispatched": dispatched,
                "rows_saved": offered - dispatched,
            }

    def _count_pooled(self, offered: int, dispatched: int) -> None:
        with self._pools_lock:
            self._pooled_dispatches += 1
            self._pooled_rows_offered += int(offered)
            self._pooled_rows_dispatched += int(dispatched)

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
        """
        if not grating.encode:
            return self._query_fn()(
                x, grating.effective_c, grating.fft_shape, grating.out_shape
            )
        enc, x_scale = self._encode(x, grating.slm_bits)
        y = self._query_fn()(
            enc, grating.effective_c, grating.fft_shape, grating.out_shape
        )
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
        # rows-major flatten, chunk folded into the score axis: one
        # readout launch per chunk
        flat = jnp.moveaxis(win, 0, 2).reshape(B, O, -1)
        return readout(flat, gidx.reshape(-1), k)

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

    def query_many(
        self,
        requests: "Sequence[tuple[FusedGrating, Array]]",
        *,
        clip_keys: "Sequence[tuple | None] | None" = None,
        dedup: bool = True,
        mesh=None,
    ) -> list[Array]:
        """Answer a mixed-tenant clip batch with one dispatch per pool group.

        ``requests`` is a sequence of ``(grating, x)`` pairs, each ``x``
        a (B_i, C, H, W, T) clip batch.  Requests are grouped by (FFT
        geometry, encode semantics, storage dtype, clip geometry); each
        group's resident gratings are packed into one pooled
        ``(ΣO, C, FH, FW, FTr)`` arena with per-tenant O-offsets
        (:class:`GratingPool`, reused across calls while the member
        gratings stay alive) and the whole group is answered with
        exactly one forward ``rfftn`` over the stacked clips, one
        channel-contracted MAC against the pool (each clip row reading
        only its own tenant's O-slice, via offset-gather — or the
        grouped Pallas ``stmul`` launch when ``use_pallas``), and one
        inverse FFT.  A mixed-tenant load of N same-geometry tenants
        thus pays 1 FFT+MAC+IFFT dispatch instead of N.

        **Clip-dedup (shared-stream fan-out).**  Within a group, rows
        whose clips hash content-equal (``clip_keys``, default computed
        via :func:`clip_key`) collapse onto *one* physical row reading
        the union of their tenants' O-slices — the paper's headline
        dataflow, many kernels correlated against one stream in
        parallel: N tenants searching the same clip pay one forward FFT
        and one MAC row instead of N.  Per-request outputs are sliced
        from the shared row's span, equal to the undeduped answer
        exactly (the MAC rows each tenant reads are identical).
        ``dedup=False`` keeps the one-row-per-request baseline.

        The gratings may come from *different* engines (mixed-fidelity
        serving): everything record-time is already folded into each
        effective grating, and the query-time semantics ride on the
        grating itself (``encode`` / ``slm_bits``), so pipelines that
        share encode semantics and geometry share one pool group.

        ``mesh`` switches the group dispatch to the sharded executor: a
        ``(data, model)`` :class:`jax.sharding.Mesh` (see
        :func:`repro.launch.mesh.make_local_mesh`) shards the arena's
        ΣO rows over the model axis and the physical clip rows over the
        data axis — each device contracts its own arena tile against
        its own clip rows, psum-free — and every request's answer is
        bitwise-equal to the single-device dispatch (see docs/mesh.md).

        Returns outputs in request order, each (B_i, O_i, *out_shape) —
        equal to ``query(grating_i, x_i)`` to float tolerance.
        """
        groups = self._group_requests(requests)
        keys = self._clip_ids(requests, clip_keys, dedup)
        results: list[Array | None] = [None] * len(requests)
        shards = int(mesh.shape["model"]) if mesh is not None else 1
        for idxs in groups.values():
            gratings = [requests[i][0] for i in idxs]
            members, slot_of = _dedup_members(gratings)
            pool = self._pool_for(members, shards)
            xs = [requests[i][1] for i in idxs]
            gkeys = [keys[i] for i in idxs]
            if mesh is not None:
                lay = self._mesh_layout(pool, gratings, slot_of, gkeys)
            else:
                lay = self._dedup_layout(pool, gratings, slot_of, gkeys)
            ux = [xs[j] for j in lay.uniq]
            x = ux[0] if len(ux) == 1 else jnp.concatenate(ux, axis=0)
            nbs = [int(xj.shape[0]) for xj in ux]
            rows = np.repeat(lay.row_of, nbs).astype(np.int32)
            self._count_pooled(sum(int(xj.shape[0]) for xj in xs), sum(nbs))
            if mesh is not None:
                proto = gratings[0]
                pool_re, pool_im = self._mesh_arena(pool, mesh)
                x_scale = None
                if proto.encode:
                    # eager, like _pooled_dispatch: jit-fusing the
                    # encode chain with the MAC rounds differently
                    x, x_scale = self._encode(x, int(proto.slm_bits))
                y = self._mesh_fns(mesh)["oneshot"](
                    x, pool_re, pool_im, x_scale,
                    fft_shape=proto.fft_shape,
                    out_shape=proto.out_shape,
                    n_out=lay.n_out,
                )
            else:
                y = self._pooled_dispatch(
                    x, pool, rows, gratings[0], n_out=lay.n_out
                )
            ub0 = np.concatenate([[0], np.cumsum(nbs)])
            for j, i in enumerate(idxs):
                b0 = int(ub0[lay.uniq_of[j]])
                nb = int(xs[j].shape[0])
                oo = lay.o_off[j]
                results[i] = y[b0 : b0 + nb, oo : oo + gratings[j].n_out]
        return results  # type: ignore[return-value]

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

    def _dedup_layout(
        self,
        pool: GratingPool,
        gratings: list[FusedGrating],
        slot_of: list[int],
        keys: list,
    ) -> "_DedupLayout":
        """Collapse group rows with content-equal clips onto shared
        physical rows.

        Each unique clip gets one physical row whose O-window is the
        *union span* of every member slice requested for that clip
        (member slots pack contiguously, so the span is one aligned
        ``[lo, lo + n_out)`` read; tenants between two requested slots
        are computed and discarded — wasted rows are bounded by the
        arena, and in the canonical all-tenants-one-stream batch the
        span is exactly the whole arena).  ``n_out`` is the widest span,
        rounded to the pool's O-tile grid for the grouped Pallas kernel;
        rows with narrower spans read tail rows the dispatch zero-pads
        (:func:`_pad_arena`).

        One static ``n_out`` for the whole dispatch is a deliberate
        trade-off: the MAC/gather (dense or Pallas) needs a uniform
        per-row width, so in a *mixed* batch (one wide shared-stream
        span next to narrow unique rows) the narrow rows compute and
        discard up to the widest span.  With no dedup, spans equal
        member slots and this reduces exactly to the pre-dedup
        ``pool.n_out`` behavior; ragged per-row widths or splitting
        wide/narrow rows into separate dispatches would cost an extra
        FFT dispatch per batch — the thing pooling exists to avoid.
        """
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
        span_lo = [None] * len(uniq)
        span_hi = [0] * len(uniq)
        for j, u in enumerate(uniq_of):
            s = pool.o_start[slot_of[j]]
            e = s + gratings[j].n_out
            span_lo[u] = s if span_lo[u] is None else min(span_lo[u], s)
            span_hi[u] = max(span_hi[u], e)
        n_out = max(hi - lo for lo, hi in zip(span_lo, span_hi))
        n_out = -(-n_out // pool.align) * pool.align
        o_off = [
            pool.o_start[slot_of[j]] - span_lo[uniq_of[j]]
            for j in range(len(uniq_of))
        ]
        return _DedupLayout(
            uniq=uniq,
            uniq_of=uniq_of,
            row_of=span_lo,
            o_off=o_off,
            n_out=n_out,
        )

    def _mesh_layout(
        self,
        pool: GratingPool,
        gratings: list[FusedGrating],
        slot_of: list[int],
        keys: list,
    ) -> "_DedupLayout":
        """Row layout of a mesh-sharded dispatch: full-arena fan-out.

        With the arena's ΣO rows sharded over the model axis, the
        offset-gather behind :meth:`_dedup_layout`'s union spans would
        be a cross-shard read; instead every physical clip row computes
        against the *entire* (sharded) arena — each model-axis device
        contracts only its own ``shard_rows`` tile, psum-free — and a
        request's answer is the slice of the global output at its
        member slot's absolute ``o_start``.  Clip-dedup degenerates to
        unique-clips-only (a shared physical row already reads every
        tenant's slice), and the "wasted" inter-slot rows are exactly
        the canonical all-tenants-one-stream batch
        :meth:`_dedup_layout` documents, spread over M devices.
        """
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
        return _DedupLayout(
            uniq=uniq,
            uniq_of=uniq_of,
            row_of=[0] * len(uniq),
            o_off=[pool.o_start[slot_of[j]] for j in range(len(uniq_of))],
            n_out=int(pool.re.shape[0]),
        )

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
    ) -> "list[Array] | list[TopKDetections]":
        """Pooled :meth:`query_stream`: one overlap-save pass per group.

        The streaming analogue of :meth:`query_many` — mixed-tenant long
        clips sharing the coherence-window geometry (same recorded
        kernel/window shapes, encode semantics and stream length) stack
        on the batch axis and every window chunk runs one pooled
        FFT+MAC+IFFT against the group arena, instead of one overlap-
        save pass per tenant.  Clip-dedup applies as in
        :meth:`query_many`: requests whose streams hash content-equal
        share one physical batch row reading the union of their O-slices
        — N tenants fanning out over one shared stream pay one forward
        FFT per window chunk, total.  Streams whose window count exceeds
        ``max_buffer_windows`` (default
        ``config.osave_max_buffer_windows``) are fed through the stream
        cursor in fixed-size T-chunks at constant peak memory.  Encoding
        stays per-example stream-global, so each request's output equals
        ``query_stream(grating_i, x_i)`` to float tolerance.

        ``readout_k`` fuses the detection readout into the pooled
        epilogue (see :meth:`query_stream`): each request gets a
        :class:`TopKDetections` instead of a volume, and the pooled
        ``(B, ΣO, H', W', T')`` buffer — the serving memory ceiling at
        large tenant pools — never materializes; only (rows, K) states
        cross window chunks and cursor segments.  Bitwise equal to
        reducing the stitched volumes, dedup union-slice rows included.

        ``mesh`` switches every group dispatch to the sharded executor
        (see :meth:`query_many`): arena ΣO rows over the model axis,
        physical stream rows over the data axis, the forward ``rfftn``
        of each stream row running once on its data shard, and the MAC
        + fused readout shard-local (psum-free).  Outputs — volumes and
        top-K states, chunked-cursor and bf16 storage included — are
        bitwise-equal to the single-device path.
        """
        groups = self._group_requests(requests, stream=True)
        keys = self._clip_ids(requests, clip_keys, dedup)
        results: list[Array | None] = [None] * len(requests)
        shards = int(mesh.shape["model"]) if mesh is not None else 1
        for idxs in groups.values():
            gratings = [requests[i][0] for i in idxs]
            g0 = gratings[0]
            if g0.ker_shape is None:
                raise ValueError(
                    "grating lacks ker_shape (recorded by an older engine); "
                    "re-record before streaming queries"
                )
            members, slot_of = _dedup_members(gratings)
            pool = self._pool_for(members, shards)
            xs = [requests[i][1] for i in idxs]
            kh, kw, kt = g0.ker_shape
            oh, ow, _ = g0.out_shape
            frame_hw = (oh + kh - 1, ow + kw - 1)
            if tuple(xs[0].shape[-3:-1]) != frame_hw:
                raise ValueError(
                    f"clip spatial dims {tuple(xs[0].shape[-3:-1])} do not "
                    f"match the recorded frame size {frame_hw}"
                )
            if mesh is not None:
                lay = self._mesh_layout(
                    pool, gratings, slot_of, [keys[i] for i in idxs]
                )
            else:
                lay = self._dedup_layout(
                    pool, gratings, slot_of, [keys[i] for i in idxs]
                )
            ux = [xs[j] for j in lay.uniq]
            nbs = [int(xj.shape[0]) for xj in ux]
            ub0 = [0]
            for nb in nbs:
                ub0.append(ub0[-1] + nb)
            rows = tuple(
                r for u, nb in enumerate(nbs) for r in [lay.row_of[u]] * nb
            )
            # per-REQUEST output splits: several requests may read
            # different O-windows of one shared physical row
            splits = tuple(
                (
                    ub0[lay.uniq_of[j]],
                    int(xs[j].shape[0]),
                    lay.o_off[j],
                    gratings[j].n_out,
                )
                for j in range(len(idxs))
            )
            self._count_pooled(sum(int(xj.shape[0]) for xj in xs), sum(nbs))
            if mesh is not None:
                # GSPMD mis-lowers a concatenate traced inside jit when
                # its result feeds a shard_map input on a 2-axis mesh —
                # each model shard receives the model-axis SUM of its
                # rows — so the physical batch is packed eagerly here
                # and the sharded drivers take exactly one array
                if len(ux) > 1:
                    ux = [jnp.concatenate(ux, axis=0)]
                # full-arena fan-out: the shard-tiled arena is read
                # whole (lay.n_out == its row count), so no padded view
                # is needed; planes live on the mesh, rows on 'model'
                pool_re, pool_im = self._mesh_arena(pool, mesh)
            else:
                # union spans can read past the arena tail: fetch the
                # (memoized) padded view so the jitted body never
                # gathers out of bounds
                max_row = max(lay.row_of) if lay.row_of else 0
                pool_re, pool_im = self._padded_arena(pool, max_row, lay.n_out)
            plan = self.stream_plan_for(g0, xs[0].shape[-1], chunk_windows)
            mbw = self._max_buffer_windows(max_buffer_windows)
            static = dict(
                rows=rows,
                splits=splits,
                ker_shape=g0.ker_shape,
                fft_shape=g0.fft_shape,
                encode=g0.encode,
                slm_bits=g0.slm_bits,
                n_out=lay.n_out,
            )
            fused = readout_k is not None
            if mesh is not None:
                fns = self._mesh_fns(mesh)
                many_fn = fns["stream_topk"] if fused else fns["stream"]
            else:
                many_fn = (
                    self._stream_many_topk_fn
                    if fused
                    else self._stream_many_fn
                )
            if fused:
                static["k"] = int(readout_k)
            oh, ow, _ = g0.out_shape
            stream_out = (oh, ow, plan.n_valid)
            if mbw is None or plan.n_blocks <= mbw:
                outs = many_fn(
                    tuple(ux), pool_re, pool_im, plan=plan, **static
                )
                if fused:
                    outs = tuple(
                        TopKDetections(s, ix, stream_out) for s, ix in outs
                    )
            else:
                # bounded-memory chunked pass: stream-global SLM scales
                # measured once, then every fixed-size segment rides the
                # same jitted pooled driver
                cursor = spectral_conv.StreamCursor(plan, mbw)
                x_scale = None
                if g0.encode:
                    scales = [_stream_scale(xj) for xj in ux]
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
                    so = many_fn(
                        tuple(xj[..., seg.t0 : seg.t1] for xj in ux),
                        pool_re,
                        pool_im,
                        x_scale,
                        plan=seg_plan,
                        **static,
                    )
                    nv_locals.append(seg_plan.n_valid)
                    t0s.append(seg.out_t0)
                    seg_outs.append(so)
                if fused:
                    # one jitted rebase+merge tail per request: local
                    # positions land in the stream-global volume and the
                    # (rows, K) states fold, without per-segment eager
                    # dispatch overhead
                    outs = tuple(
                        TopKDetections(
                            *self._seg_merge_fn(
                                tuple(so[r][0] for so in seg_outs),
                                tuple(so[r][1] for so in seg_outs),
                                k=int(readout_k),
                                nv_locals=tuple(nv_locals),
                                t0s=tuple(t0s),
                                nv_total=plan.n_valid,
                            ),
                            stream_out,
                        )
                        for r in range(len(splits))
                    )
                else:
                    outs = tuple(
                        jnp.concatenate([so[r] for so in seg_outs], axis=-1)
                        if len(seg_outs) > 1
                        else seg_outs[0][r]
                        for r in range(len(splits))
                    )
            for j, i in enumerate(idxs):
                results[i] = outs[j]
        return results  # type: ignore[return-value]

    def _group_requests(self, requests, stream: bool = False) -> dict:
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
                g.ker_shape if stream else None,
                bool(g.encode),
                int(g.slm_bits) if g.encode else -1,
                g.storage_dtype,
                tuple(x.shape[1:]),
                str(x.dtype),
            )
            groups.setdefault(key, []).append(i)
        return groups

    def _pool_align(self) -> int:
        """O-offset alignment of the pool arena: the Pallas grouped
        kernel indexes the arena in O-tile units, so member slots must
        start on its ``block_o`` grid; the dense gather path needs no
        alignment."""
        cfg = self.config
        if not getattr(cfg, "use_pallas", False):
            return 1
        from repro.kernels.stmul import kernel as stmul_kernel  # lazy

        return int(
            getattr(cfg, "stmul_block_o", None) or stmul_kernel.BLOCK_O
        )

    def _pool_for(
        self, members: list[FusedGrating], shards: int = 1
    ) -> "GratingPool":
        """Fetch or build the packed arena for this member list.

        Pools are memoized per (member identity, alignment, shard
        count): gratings are immutable once recorded, so object identity
        is content identity, and the entry holds strong references to
        its members — the arena is a *stable* device buffer reused
        across dispatches instead of being re-packed per batch.  A small
        LRU bound keeps retired membership sets (tenant churn) from
        pinning dead gratings.  ``shards`` selects the mesh-aware
        shard-tiled packing (see :func:`_build_pool`); the same member
        set sharded differently is a different arena.
        """
        align = self._pool_align()
        key = (tuple(id(g) for g in members), align, int(shards))
        with self._pools_lock:
            pool = self._pools.get(key)
            if pool is not None:
                self._pools.move_to_end(key)
                return pool
        pool = _build_pool(members, align, shards)
        with self._pools_lock:
            self._pools[key] = pool
            while len(self._pools) > self._max_pools:
                self._pools.popitem(last=False)
        return pool

    def _padded_arena(
        self, pool: "GratingPool", max_row: int, n_out: int
    ) -> tuple[Array, Array]:
        """The pool planes, row-padded for ``[row, row + n_out)`` reads —
        memoized per (pool, rows needed) so recurring dedup compositions
        reuse one padded device buffer (the un-padded common case returns
        the pool's own planes untouched)."""
        need = int(max_row) + int(n_out) - int(pool.re.shape[0])
        if need <= 0:
            return pool.re, pool.im
        key = (id(pool), int(max_row) + int(n_out))
        with self._pools_lock:
            hit = self._padded.get(key)
            if hit is not None:
                self._padded.move_to_end(key)
                return hit[1], hit[2]
        re, im = _pad_arena(pool.re, pool.im, max_row, n_out)
        with self._pools_lock:
            self._padded[key] = (pool, re, im)
            while len(self._padded) > self._max_pools:
                self._padded.popitem(last=False)
        return re, im

    # -- mesh-sharded execution (query_many/query_stream_many mesh=) -------

    def _mesh_arena(self, pool: "GratingPool", mesh) -> tuple[Array, Array]:
        """The pool planes placed on the mesh — arena rows sharded over
        the model axis via the serving rules' ``grating`` logical axis —
        memoized per (pool, mesh) so the arena ships to the devices once
        per membership, not once per dispatch.  Entries pin the pool
        (strong ref: id-keyed lookups stay sound)."""
        key = (id(pool), mesh)
        with self._pools_lock:
            hit = self._mesh_arenas.get(key)
            if hit is not None:
                self._mesh_arenas.move_to_end(key)
                return hit[1], hit[2]
        from repro.distributed import sharding as shardlib  # lazy

        rules = shardlib.make_serving_rules()
        spec = shardlib.spec_for(
            pool.re.shape,
            ("grating",) + (None,) * (pool.re.ndim - 1),
            rules,
            mesh,
        )
        sharding = jax.sharding.NamedSharding(mesh, spec)
        re = jax.device_put(pool.re, sharding)
        im = jax.device_put(pool.im, sharding)
        with self._pools_lock:
            self._mesh_arenas[key] = (pool, re, im)
            while len(self._mesh_arenas) > self._max_pools:
                self._mesh_arenas.popitem(last=False)
        return re, im

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
        ``_chunk_topk`` / ``_fold_chunk_states`` verbatim with
        ``rows=(0,)*B_local`` over its local arena tile, so every
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
            cost compute on their shard and are sliced away by the
            per-request splits (scale pads to 1 — encode of an all-zero
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
            xs, pool_re, pool_im, x_scale=None, *, rows, splits,
            ker_shape, fft_shape, plan, encode, slm_bits, n_out,
        ):
            # `rows` rides the signature for trace-cache parity with the
            # single-device driver but is all-zero in mesh mode (full-
            # arena fan-out); `n_out` is the whole arena's row count.
            del rows
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
                    (xl,), prl, pil, xsl,
                    rows=(0,) * b_local, ker_shape=ker_shape,
                    fft_shape=fft_shape, plan=plan, encode=encode,
                    slm_bits=slm_bits, n_out=s_local,
                )
                starts = spectral_conv.window_starts(plan)
                blocks = lax.map(
                    lambda cs: jax.vmap(one_window)(cs), starts
                )
                y = spectral_conv.stitch_windows(blocks, plan)
                if xs_l is not None:
                    y = y * xs_l
                return y

            y = run(body, x, pool_re, pool_im, x_scale, P("data", "model"))
            return tuple(
                y[b0 : b0 + nb, oo : oo + o] for b0, nb, oo, o in splits
            )

        def stream_many_topk(
            xs, pool_re, pool_im, x_scale=None, *, rows, splits,
            ker_shape, fft_shape, plan, encode, slm_bits, n_out, k,
        ):
            del rows
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
                    (xl,), prl, pil, xsl,
                    rows=(0,) * b_local, ker_shape=ker_shape,
                    fft_shape=fft_shape, plan=plan, encode=encode,
                    slm_bits=slm_bits, n_out=s_local,
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
            s, i = run(body, x, pool_re, pool_im, x_scale, (spec, spec))
            return tuple(
                (s[b0 : b0 + nb, oo : oo + o], i[b0 : b0 + nb, oo : oo + o])
                for b0, nb, oo, o in splits
            )

        def oneshot(
            x, pool_re, pool_im, x_scale=None, *, fft_shape,
            out_shape, n_out,
        ):
            # runs UN-jitted: the single-device one-shot dispatch is
            # eager op-by-op, and wrapping the sharded body in jit lets
            # XLA contract the bf16-upcast MAC differently (FMA in the
            # fused complex multiply) — eager shard_map keeps the same
            # op boundaries and is bitwise-equal; encode likewise
            # happens eagerly in the caller
            x, x_scale = pad_b(x, x_scale)
            del n_out  # per-shard width = the local tile's own row count
            qfn = self._pooled_query_shard_fn()

            def body(xl, prl, pil, xsl):
                y = qfn(xl, prl, pil, fft_shape, out_shape)
                return y if xsl is None else y * xsl

            return run(body, x, pool_re, pool_im, x_scale, P("data", "model"))

        return {
            "stream": jax.jit(
                stream_many,
                static_argnames=(
                    "rows", "splits", "ker_shape", "fft_shape", "plan",
                    "encode", "slm_bits", "n_out",
                ),
            ),
            "stream_topk": jax.jit(
                stream_many_topk,
                static_argnames=(
                    "rows", "splits", "ker_shape", "fft_shape", "plan",
                    "encode", "slm_bits", "n_out", "k",
                ),
            ),
            "oneshot": oneshot,
        }

    def _pooled_dispatch(
        self,
        x: Array,
        pool: "GratingPool",
        rows: np.ndarray,
        proto: FusedGrating,
        n_out: int | None = None,
    ) -> Array:
        """One pooled FFT+MAC+IFFT (+ the group's encode epilogue).

        ``proto`` is any member grating — the group key guarantees they
        share geometry and encode semantics.  ``n_out`` widens the
        per-row read past the widest member slot when clip-dedup rows
        cover union spans (default: the pool's slot width)."""
        if n_out is None:
            n_out = pool.n_out
        max_row = int(np.max(rows)) if len(rows) else 0
        pool_re, pool_im = self._padded_arena(pool, max_row, n_out)
        rows = jnp.asarray(rows, jnp.int32)
        query = self._pooled_query_fn()
        if not proto.encode:
            return query(
                x, pool_re, pool_im, rows, n_out,
                proto.fft_shape, proto.out_shape,
            )
        enc, x_scale = self._encode(x, proto.slm_bits)
        y = query(
            enc, pool_re, pool_im, rows, n_out,
            proto.fft_shape, proto.out_shape,
        )
        return y * x_scale

    def _stream_many_impl(
        self, xs, pool_re, pool_im, x_scale=None,
        *, rows, splits, ker_shape, fft_shape, plan, encode, slm_bits, n_out,
    ):
        """Pooled overlap-save body (jitted; mirrors ``_stream_impl``).

        ``xs`` is the tuple of per-physical-copy clip batches (stacked
        in-trace so the eager path dispatches nothing; clip-dedup means
        one entry may serve several requests); ``rows`` the static
        per-row arena offsets, ``splits`` the static per-request
        ``(b0, nb, o_off, O_i)`` output partition (``o_off`` slices the
        request's O-window out of its shared row's union span).
        ``x_scale`` carries precomputed stream-global SLM scales when
        the clips are cursor segments of longer streams."""
        one_window, win_out, x_scale = self._pooled_osave_setup(
            xs, pool_re, pool_im, x_scale,
            rows=rows, ker_shape=ker_shape, fft_shape=fft_shape,
            plan=plan, encode=encode, slm_bits=slm_bits, n_out=n_out,
        )
        starts = spectral_conv.window_starts(plan)
        blocks = lax.map(lambda cs: jax.vmap(one_window)(cs), starts)
        y = spectral_conv.stitch_windows(blocks, plan)
        if x_scale is not None:
            y = y * x_scale
        return tuple(
            y[b0 : b0 + nb, oo : oo + o] for b0, nb, oo, o in splits
        )

    def _pooled_osave_setup(
        self, xs, pool_re, pool_im, x_scale,
        *, rows, ker_shape, fft_shape, plan, encode, slm_bits, n_out,
    ):
        """Shared front half of the pooled overlap-save bodies: stack
        the per-copy clips, encode (stream-global scale), pad the time
        axis and build the per-window pooled query closure (grouped
        Pallas launch under ``use_pallas``, hoisted-gather einsum
        otherwise).  Returns (one_window, win_out, x_scale)."""
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
            query = self._pooled_query_fn()

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
        self, xs, pool_re, pool_im, x_scale=None,
        *, rows, splits, ker_shape, fft_shape, plan, encode, slm_bits,
        n_out, k,
    ):
        """Fused-readout pooled overlap-save body (jitted): the window
        loop of ``_stream_many_impl`` with the stitch replaced by the
        per-chunk top-K reduction — the pooled ``(B, n_out, H', W', T')``
        volume (the serving memory ceiling at large tenant pools) never
        materializes.  Per-request slicing commutes with the per-(row,
        kernel) reduction, so dedup union-span states split exactly like
        volumes.  Returns a tuple of (scores, index) per request,
        positions local to this call's valid range."""
        one_window, win_out, x_scale = self._pooled_osave_setup(
            xs, pool_re, pool_im, x_scale,
            rows=rows, ker_shape=ker_shape, fft_shape=fft_shape,
            plan=plan, encode=encode, slm_bits=slm_bits, n_out=n_out,
        )
        readout = self._readout_fn()

        def one_chunk(cs):
            win = jax.vmap(one_window)(cs)
            return self._chunk_topk(
                win, cs, plan, win_out, x_scale, readout, k
            )

        starts = spectral_conv.window_starts(plan)
        chunk_s, chunk_i = lax.map(one_chunk, starts)
        s, i = self._fold_chunk_states(chunk_s, chunk_i, k)
        return tuple(
            (s[b0 : b0 + nb, oo : oo + o], i[b0 : b0 + nb, oo : oo + o])
            for b0, nb, oo, o in splits
        )

    def _pooled_query_fn(self):
        """The per-group pooled FFT+MAC+IFFT: dense offset-gather einsum
        by default, the grouped Pallas stmul launch under ``use_pallas``."""
        cfg = self.config
        if not getattr(cfg, "use_pallas", False):
            return _pooled_query_dense
        from repro.kernels.stmul import ops as stmul_ops  # lazy import

        min_mxu_c = getattr(cfg, "stmul_min_mxu_c", None)
        tiles = dict(
            block_o=getattr(cfg, "stmul_block_o", None),
            block_f=getattr(cfg, "stmul_block_f", None),
        )

        def query(x, pool_re, pool_im, rows, n_out, fft_shape, out_shape):
            return stmul_ops.query_grating_pooled(
                x, pool_re, pool_im, rows, n_out, fft_shape, out_shape,
                min_mxu_c=min_mxu_c, **tiles,
            )

        return query

    def _pooled_query_shard_fn(self):
        """Shard-local pooled FFT+MAC+IFFT for the mesh bodies: every
        clip row reads the local arena tile whole (zero offsets) —
        ``stmul_ops.pooled_query_shard`` under ``use_pallas``, the dense
        offset-gather einsum at offset 0 otherwise."""
        cfg = self.config
        if not getattr(cfg, "use_pallas", False):

            def dense(x, pool_re, pool_im, fft_shape, out_shape):
                rows = jnp.zeros((x.shape[0],), jnp.int32)
                return _pooled_query_dense(
                    x, pool_re, pool_im, rows, int(pool_re.shape[0]),
                    fft_shape, out_shape,
                )

            return dense
        from repro.kernels.stmul import ops as stmul_ops  # lazy import

        min_mxu_c = getattr(cfg, "stmul_min_mxu_c", None)
        tiles = dict(
            block_o=getattr(cfg, "stmul_block_o", None),
            block_f=getattr(cfg, "stmul_block_f", None),
        )

        def query(x, pool_re, pool_im, fft_shape, out_shape):
            return stmul_ops.pooled_query_shard(
                x, pool_re, pool_im, fft_shape, out_shape,
                min_mxu_c=min_mxu_c, **tiles,
            )

        return query

    # -- internals ---------------------------------------------------------

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
