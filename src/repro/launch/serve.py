"""Serving drivers.

Two serving modes, matching the paper's system and the LM zoo:

1. **Multi-tenant STHC video event search** (`VideoSearchServer`) — the
   paper's deployment (Fig. 1C), record-once / stream-forever, and
   since PR 5 **stream-centric** rather than request-centric: the unit
   the hot path optimizes for is the *shared video stream* that many
   tenants search in parallel (the paper's headline — 30×40×8-tap
   kernel banks correlated against one stream simultaneously), not the
   individual request.  Each *tenant* is a named reference kernel set
   ("what to look for"), recorded into one shared content-hash
   :class:`GratingCache` with an LRU budget in entries *and* grating
   bytes.  Evicted tenants re-record transparently on their next query
   (a cache miss), exactly like re-writing the atomic medium.

   Tenants are heterogeneous on three axes, all coexisting on one
   server and one shared cache:

   * **fidelity** — each kernel set registers with its own
     :class:`~repro.core.fidelity.FidelityPipeline` (``add_tenant`` /
     ``add_kernel_set``, default = ``VideoSearchConfig.fidelity``).
   * **device model** — ``add_tenant(..., slm=..., atoms=...)`` gives a
     tenant its own SLM / atomic-medium configuration.  The server
     keeps one mode-agnostic engine per distinct **(fidelity
     fingerprint, device fingerprint)** pair, and the cache keys every
     grating on both — no cross-fidelity or cross-device cache hits.
   * **storage** — gratings store f32 or split-real bf16
     (``grating_dtype``), halving the cache bytes per tenant.

   The serving hot path is a three-stage **queue → batcher →
   pooled-executor** architecture, stream-centric at every stage:

   * **queue** — :class:`MicrobatchScheduler` fronts the server with a
     *bounded* async request queue: ``submit()`` returns a future and
     fingerprints the clip bytes once (the content hash the dedup
     rides on); admission control sheds requests the moment the queue
     is full (``RequestRejected`` + a rejected-request counter) or,
     with ``block=True``, exerts backpressure on the caller.
     Scheduler ``metrics()`` report end-to-end latency percentiles
     (p50/p90/p99), queue depth, shed/batch counters, and dedup-group
     stats.
   * **batcher** — the scheduler thread drains the queue into
     microbatches (up to ``max_batch`` requests, waiting
     ``batch_wait_s`` after the first arrival so a fuller batch can
     form), grouping *across tenants* by clip shape and arranging
     same-clip requests into adjacent **dedup groups**.
   * **pooled executor** — ``search_batch`` hands the mixed-tenant
     microbatch to the engine's pooled path
     (``QueryEngine.query_stream_many``): every resident tenant grating
     sharing the window FFT geometry and encode semantics is packed
     into one stationary ``(ΣO, C, FH, FW, FTr)`` arena, and the whole
     batch is answered with **one** FFT + pooled spectral MAC + IFFT
     per coherence-window chunk instead of one dispatch chain per
     tenant (the Morph-style heterogeneous-batch win; a per-tenant
     sequential path is kept as the benchmark baseline,
     ``pooled=False``).  Three stream-centric refinements ride the
     pooled dispatch:

     - **clip-dedup** — requests whose clips hash content-equal share
       *one* physical batch row reading the union of their tenants'
       O-slices, so N tenants fanning out over one shared stream pay
       one forward FFT total instead of N
       (``VideoSearchConfig.dedup_clips``; counters in ``metrics()``).
     - **bounded-memory chunking** — streams whose coherence-window
       count exceeds ``VideoSearchConfig.max_buffer_windows`` are fed
       through a :class:`~repro.core.spectral_conv.StreamCursor` in
       fixed-size T-chunks with kt−1-frame carry-over tails: clips
       longer than one device buffer serve at constant *input*-side
       memory, exactly equal to the one-shot correlation.
     - **fused detection readout** (``fused_readout``, default on) —
       the *output* side goes constant-memory too: the per-tenant
       peak / top-K (score, position) reduction is folded into the
       overlap-save epilogue (``readout_k`` on the engine's streaming
       drivers, backed by the tiled ``topk_readout`` kernel in
       ``kernels/stmul``), so each window chunk collapses in-kernel to
       a tiny ``(rows, K)`` running state and the stitched
       ``(B, O, H', W', T')`` correlation volume — the old memory
       ceiling at large tenant pools × long streams — never
       materializes on the serving path.

   **Per-path memory model** (what materializes where): the input side
   holds one cursor segment (``max_buffer_windows`` coherence windows);
   the output side holds, *stitched*, the full
   ``rows × O × H' × W' × T'`` volume (grows linearly with stream
   length and pool size — kept for ``return_volume=True`` and as the
   fused path's equivalence oracle) vs, *fused*, one window chunk's
   ``rows × O × H' × W' × (chunk·step)`` scores that die inside the
   chunk reduction plus ``rows × O × K`` running states.  The running
   states merge associatively across chunks and cursor segments under
   a total selection order (score desc, earliest position first), so
   the fused result is **bitwise** the stitched volume's max / argmax /
   top-K — an arbitrarily long stream with hundreds of resident
   kernels serves at O(chunk) memory end to end.

   `metrics()` reports cache hits/misses/evictions/bytes, per-tenant
   fidelity + device labels, pooled/sequential dispatch counters,
   clip-dedup row savings, and measured windows/s + frames/s against
   the paper's projected loader rates (`core.throughput`).

   **Failure semantics** (PR 6, the serving-resilience layer — see
   :mod:`repro.launch.resilience` for the primitives, and
   ``docs/serving.md`` for the consolidated contract including the
   replicated layer above this one: :mod:`repro.launch.replica` fronts
   N of these servers with heartbeat-driven failover, request hedging,
   and durable warm restart):

   * *Error taxonomy* — every failure a future can resolve with is a
     typed :class:`~repro.launch.resilience.ServingError` carrying the
     ``tenant`` and ``batch_id`` it happened in:
     ``RequestRejected`` (admission control shed the request),
     ``DeadlineExceeded`` (deadline passed before a result was ready),
     ``TenantQuarantined`` (signal-integrity guard isolated this
     tenant's rows from a pooled batch), ``BatchExecutionError``
     (dispatch failed after retries; root cause in ``__cause__``), and
     ``SchedulerClosed`` (shutdown resolved a queued request).
     Caller errors (``ValueError`` / ``KeyError`` / ``TypeError`` from
     request validation) pass through unwrapped — they would fail
     identically on every retry and every ladder rung.
   * *Request lifecycle* — ``submit(..., deadline_s=...)`` attaches a
     deadline (default ``MicrobatchScheduler(default_deadline_s=...)``,
     None = no deadline); it is enforced at dispatch (expired requests
     are pruned before burning device time), across the retry loop, and
     — the backstop — by a **watchdog thread** that resolves any
     still-pending future at its deadline.  Every submitted future
     resolves with a result or a typed error: no hangs, ever.
     Transient failures (``exc.transient`` truthy, e.g. an injected
     chaos fault) are retried under a seeded decorrelated-jitter
     backoff (``RetryPolicy``, deterministic schedule per dispatch).
   * *Degradation ladder* — dispatch modes ``pooled → sequential →
     single``, the first two behind per-mode circuit breakers
     (``failure_threshold`` consecutive failures trip open →
     ``recovery_s`` later a half-open probe → success closes).  While
     the pooled path's breaker is open the scheduler serves every batch
     in the degraded mode — requests keep completing, slower — and
     recovers to pooled automatically.  ``metrics()`` reports the
     current ``mode``, per-breaker state + trip/recovery counters
     (``ladder``), ``deadline_missed``, ``retries``, ``quarantined``,
     and ``watchdog_expired``.
   * *Signal integrity* — the server finite-checks every request's
     correlation scores before delivery (``guard_scores``): a NaN/Inf
     row quarantines *that tenant's request* (``TenantQuarantined``)
     while the rest of the pooled batch delivers bitwise-identical to a
     fault-free run.  ``verify_gratings`` adds a content checksum to
     every cache fetch: a corrupted resident grating is discarded and
     transparently re-recorded (off by default — it costs a device
     reduction per fetch — and enabled by the chaos suite).
   * *Chaos* — ``server.chaos`` accepts a
     :class:`~repro.distributed.fault.ChaosInjector`; the hot path
     fires its seams (``cache_fetch``, ``encode``, ``dispatch``,
     ``readout``) so `benchmarks/chaos.py` can storm the stack with
     exceptions, NaN payloads, latency spikes, and eviction races.
     With no injector attached each seam is one attribute check.

2. **LM serving** (`LMServer`) — prefill + decode with the uniform cache
   API; used by the serve smoke tests and the decode dry-run shapes.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import queue as queue_mod
import threading
import time
import warnings
from concurrent.futures import Future
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import atomic, fidelity as fidelity_mod, optics
from repro.core import hybrid, throughput
from repro.core.engine import (
    TOPK_EMPTY_IDX,
    GratingCache,
    clip_key,
    clip_keys_for,
)
from repro.core.fidelity import FidelityPipeline
from repro.core.sthc import STHC, STHCConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.resilience import (
    BatchExecutionError,
    DeadlineExceeded,
    DegradationLadder,
    ReplicaUnavailable,  # noqa: F401  (re-exported serving taxonomy)
    RequestRejected,
    RetryPolicy,
    SchedulerClosed,
    ServingError,
    TenantQuarantined,
    Watchdog,
    is_transient,
    is_validation_error,
    resolve_exception,
    resolve_result,
)
from repro.models import model_api

PyTree = Any


# ---------------------------------------------------------------------------
# STHC video search serving (multi-tenant)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class VideoSearchConfig:
    """Multi-tenant video-search serving knobs.

    Attributes:
      window_frames: coherence window T2 (frames) — the streaming FFT
        geometry every tenant is recorded at.
      mode: DEPRECATED two-way fidelity switch (``'ideal'`` |
        ``'physical'``); maps to the matching pipeline preset with a
        ``DeprecationWarning``.  Use ``fidelity=``.
      fidelity: the server's *default* fidelity pipeline — the stack of
        typed physics stages (:mod:`repro.core.fidelity`) tenants record
        and query through unless they register with their own
        (``add_tenant(..., fidelity=...)``).  None = ``ideal()``.
      chunk_windows: coherence windows correlated per step as one vmap'd
        batch (batched FFTs); 1 = strictly sequential, minimum peak
        memory.
      cache_entries / cache_bytes: LRU budget of the shared grating
        cache, in recorded kernel sets and in grating bytes (None = no
        byte cap).  Eviction re-records on the next query.  The cache is
        shared *across fidelities*: keys include the pipeline
        fingerprint, so mixed-fidelity tenants never cross-hit.
      use_pallas: route the spectral MAC through the stmul kernel.
      pooled_queries: serve mixed-tenant batches through the engine's
        pooled cross-tenant executor (one FFT + pooled MAC + IFFT per
        window chunk for every same-geometry tenant in the batch).
        False = the per-tenant-sequential dispatch loop (the benchmark
        baseline).
      dedup_clips: collapse pooled-batch rows whose clips hash
        content-equal onto one shared physical row (the shared-stream
        fan-out: N tenants searching the same clip pay one forward FFT
        total).  False = one row per request (the benchmark baseline).
      max_buffer_windows: serve at most this many coherence windows
        from one device buffer; longer streams go through the stream
        cursor in fixed-size T-chunks with carry-over tails (constant
        peak memory, exact output).  None = whole stream in one buffer.
      grating_dtype: storage precision of recorded gratings ('float32'
        | 'bfloat16').  bf16 stores split-real planes at half the HBM —
        the shared cache byte budget holds ~2x the tenants — with f32
        accumulation at the MAC.
      slm / atoms: the server's *default* device model — tenants record
        and query through these SLM / atomic-medium configurations
        unless they register with their own (``add_tenant(..., slm=...,
        atoms=...)``).  None = the library defaults.
      fused_readout: fold the detection readout (peak / top-K score +
        position per tenant kernel) into the engine's overlap-save
        epilogue: every window chunk collapses in-kernel to a tiny
        (rows, K) running state and the ``(B, O, H', W', T')``
        correlation volume never materializes on the serving path —
        peak output-side memory is O(chunk), independent of stream
        length and tenant count.  Scores/positions are bitwise what the
        stitched volume's max/argmax would report.  False = the
        stitched-volume path (the equivalence oracle and the benchmark
        baseline); ``search_batch(..., return_volume=True)`` also
        forces it for that call.
      readout_topk: detections reported per (stream, kernel) on the
        fused path (adds ``topk_scores`` / ``topk_frames`` to results
        when > 1).  Selection order is total — score descending, then
        earliest flat position — so k = 1 is exactly the stitched
        argmax.
      readout_block_o / readout_block_l: fused-readout kernel tile
        overrides (None = kernel defaults), the ``stmul_block_*``-style
        knobs for the readout launch; swept in
        ``benchmarks/kernels_bench.py``.  Only consulted under
        ``use_pallas``.
      guard_scores: finite-check every request's correlation scores
        before delivery; a NaN/Inf row resolves that request with
        ``TenantQuarantined`` instead of poisoning the pooled batch.
        The check runs on the already-host-materialized peak arrays —
        no extra device work (on the fused path a NaN anywhere in a
        row's never-materialized volume still propagates into its
        peak slot, so quarantine semantics are unchanged).
      verify_gratings: checksum-verify every grating fetched from the
        shared cache against the sum recorded at insertion; a mismatch
        (bit rot, NaN corruption, eviction race) discards the entry and
        transparently re-records.  Off by default: it costs one device
        reduction + host sync per fetch (the chaos suite turns it on).
      mesh_shape: ``(data, model)`` device-mesh shape for intra-replica
        sharded serving, or None (single-device, the default).  When
        set, the server owns one :class:`jax.sharding.Mesh` (built via
        :func:`repro.launch.mesh.make_local_mesh` at construction — the
        process must expose ``data*model`` devices, e.g. via
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set
        *before* any jax import) and every pooled dispatch shards the
        grating arena over the model axis and the stream rows over the
        data axis (``QueryEngine.query_stream_many(mesh=...)``); scores
        stay bitwise-equal to single-device serving.  See docs/mesh.md.
    """

    window_frames: int = 64
    mode: str | None = None
    fidelity: FidelityPipeline | None = None
    chunk_windows: int = 4
    cache_entries: int = 8
    cache_bytes: int | None = None
    use_pallas: bool = False
    pooled_queries: bool = True
    dedup_clips: bool = True
    max_buffer_windows: int | None = None
    fused_readout: bool = True
    readout_topk: int = 1
    readout_block_o: int | None = None
    readout_block_l: int | None = None
    grating_dtype: str = "float32"
    slm: optics.SLMConfig | None = None
    atoms: atomic.AtomicConfig | None = None
    guard_scores: bool = True
    verify_gratings: bool = False
    mesh_shape: tuple[int, int] | None = None

    def __post_init__(self) -> None:
        """Structural validation of the mesh request, at config time
        (device-count fit is enforced by ``make_local_mesh`` at server
        construction, where jax devices may legitimately be consulted)."""
        ms = self.mesh_shape
        if ms is None:
            return
        if (
            not isinstance(ms, (tuple, list))
            or len(ms) != 2
            or not all(isinstance(a, int) and not isinstance(a, bool) for a in ms)
        ):
            raise ValueError(
                "mesh_shape must be a (data, model) pair of ints, got "
                f"{ms!r}"
            )
        if any(a < 1 for a in ms):
            raise ValueError(
                f"mesh_shape axes must be >= 1, got {tuple(ms)}"
            )
        self.mesh_shape = tuple(ms)


@dataclasses.dataclass
class _Tenant:
    """Per-tenant kernels + serving counters."""

    # (O, C, kh, kw, kt) reference events, held host-side: device
    # residency stays bounded by the cache byte budget — the array is
    # only shipped back to the accelerator on a re-record (cache miss)
    kernels: np.ndarray | None
    kt: int
    channels: int = 1  # C, pinned so mismatched clips fail upfront
    # record geometry snapshotted at registration: the live cfg is a
    # mutable dataclass, and a re-record must reproduce the geometry the
    # key was hashed for, not whatever cfg says now
    signal_shape: tuple[int, int, int] | None = None
    key: tuple | None = None  # cache key, hashed once at registration
    # the tenant's correlator: one per fidelity fingerprint, pooled on
    # the server, all sharing the server's grating cache
    sthc: STHC | None = None
    # display label of the pipeline *as registered* — engines pool by
    # fingerprint (names excluded), so metrics must not read a label off
    # the shared engine: two same-physics pipelines with different names
    # would report the first registrant's name for both
    fidelity_label: str = ""
    # display label of the tenant's device model (SLM / atoms overrides)
    device_label: str = "default"
    queries: int = 0
    windows: int = 0
    frames: int = 0
    seconds: float = 0.0


class VideoSearchServer:
    """Record reference kernel sets once; stream queries through the
    engine's overlap-save path — one shared grating cache, many tenants.

    Gratings are *not* pinned on the server: every search fetches the
    tenant's grating through the cache, so a tenant evicted under the
    entry/byte budget is transparently re-recorded on its next query
    (miss), exactly like re-writing the medium.  Query throughput is
    bounded by the frame-loading rate (`core.throughput`), not by the
    correlation itself; ``chunk_windows`` trades peak activation memory
    for batched window FFTs.
    """

    def __init__(
        self,
        kernels: jax.Array | None = None,  # optional bootstrap tenant
        frame_hw: tuple[int, int] = (60, 80),
        cfg: VideoSearchConfig | None = None,
    ):
        # `None` + default-factory: a shared mutable default instance
        # would leak cfg mutations across every server construction.
        self.cfg = cfg = cfg if cfg is not None else VideoSearchConfig()
        self.frame_hw = tuple(frame_hw)
        # intra-replica device mesh: built once here (per-replica mesh
        # ownership — each replica's build_server() call constructs its
        # own server and with it its own Mesh) and threaded into every
        # pooled dispatch.  make_local_mesh raises a descriptive error
        # when the process exposes fewer than data*model devices.
        self.mesh = None
        if getattr(cfg, "mesh_shape", None) is not None:
            self.mesh = make_local_mesh(*cfg.mesh_shape)
        self.cache = GratingCache(
            max_entries=cfg.cache_entries,
            max_bytes=cfg.cache_bytes,
            verify=getattr(cfg, "verify_gratings", False),
        )
        # optional ChaosInjector (distributed.fault); when attached the
        # hot path fires its seams — when None each seam is one attr check
        self.chaos = None
        self._quarantined = 0  # guarded-by: _lock
        # one mode-agnostic engine per distinct (fidelity fingerprint,
        # device fingerprint) pair, all sharing the one grating cache
        # (mixed-fidelity + per-tenant-device serving)
        self._sthcs: dict[tuple, STHC] = {}  # guarded-by: _pool_lock
        self._pool_lock = threading.Lock()
        self._default_fidelity = self._resolve_cfg_fidelity(cfg)
        # the default-fidelity/-device correlator, kept as an attribute
        # for introspection and the LM/video demo drivers
        self.sthc = self._sthc_for(self._default_fidelity)
        self._tenants: dict[str, _Tenant] = {}  # guarded-by: _lock
        # traffic from removed/replaced tenants — server-wide totals and
        # the measured-vs-projected rates must survive tenant churn
        self._retired = _Tenant(kernels=None, kt=0)
        # guards _tenants membership and the per-tenant counters; the
        # correlation itself runs outside (the cache has its own lock)
        self._lock = threading.Lock()
        self._pooled_dispatches = 0  # guarded-by: _lock
        self._sequential_dispatches = 0  # guarded-by: _lock
        # the ONE stitched-volume detection readout, shared by every
        # entry point that still materializes volumes (fused_readout
        # off, or return_volume=True): peak + argmax of every group in
        # one jitted call.  Routing both the pooled and the sequential
        # path through this single helper keeps their scores
        # bitwise-identical (regression-tested); the fused path computes
        # the same reduction in-kernel instead.
        self._readout = jax.jit(
            lambda fmaps: tuple(
                (
                    jnp.max(f.reshape(f.shape[0], f.shape[1], -1), -1),
                    jnp.argmax(f.reshape(f.shape[0], f.shape[1], -1), -1),
                )
                for f in fmaps
            )
        )
        if kernels is not None:
            self.add_tenant("default", kernels)

    # -- engine pool (one per fidelity fingerprint) -------------------------

    @staticmethod
    def _resolve_cfg_fidelity(cfg: VideoSearchConfig) -> FidelityPipeline:
        if cfg.fidelity is not None:
            if cfg.mode is not None:
                raise ValueError(
                    "pass either the deprecated VideoSearchConfig.mode or "
                    "fidelity, not both"
                )
            return cfg.fidelity
        if cfg.mode is not None:
            pipe = fidelity_mod.from_mode(cfg.mode)  # raises on bad strings
            warnings.warn(
                "VideoSearchConfig(mode=...) is deprecated; pass "
                "fidelity=fidelity.ideal() / fidelity.physical() instead",
                DeprecationWarning,
                stacklevel=3,
            )
            return pipe
        return fidelity_mod.ideal()

    def _resolve_device(
        self,
        slm: optics.SLMConfig | None,
        atoms: atomic.AtomicConfig | None,
    ) -> tuple[optics.SLMConfig, atomic.AtomicConfig]:
        """Tenant override → server default → library default."""
        if slm is None:
            slm = self.cfg.slm if self.cfg.slm is not None else optics.SLMConfig()
        if atoms is None:
            atoms = (
                self.cfg.atoms
                if self.cfg.atoms is not None
                else atomic.AtomicConfig()
            )
        return slm, atoms

    def _sthc_for(
        self,
        pipe: FidelityPipeline,
        slm: optics.SLMConfig | None = None,
        atoms: atomic.AtomicConfig | None = None,
    ) -> STHC:
        """The pooled correlator serving one (fidelity, device model)
        pair — engines are keyed by the pipeline *fingerprint* (display
        names don't split the pool) plus the resolved SLM/atomic device
        configs (frozen dataclasses: the device fingerprint), created
        lazily, and all share ``self.cache``.  Tenants on different
        device models still pool into one dispatch whenever their
        gratings' *encode semantics* match — the engine groups by
        (geometry, encode, slm_bits), and record-time device physics is
        already baked into each effective grating."""
        slm, atoms = self._resolve_device(slm, atoms)
        key = (pipe.fingerprint(), slm, atoms)
        with self._pool_lock:
            sthc = self._sthcs.get(key)
            if sthc is None:
                sthc = STHC(
                    STHCConfig(
                        fidelity=pipe,
                        slm=slm,
                        atoms=atoms,
                        use_pallas=self.cfg.use_pallas,
                        osave_chunk_windows=self.cfg.chunk_windows,
                        osave_max_buffer_windows=getattr(
                            self.cfg, "max_buffer_windows", None
                        ),
                        # serving never runs the unfused ± reference
                        # path: drop the raw stack so each cached grating
                        # charges only its hot-path bytes against
                        # cache_bytes.
                        keep_stacked=False,
                        grating_dtype=getattr(
                            self.cfg, "grating_dtype", "float32"
                        ),
                        readout_block_o=getattr(
                            self.cfg, "readout_block_o", None
                        ),
                        readout_block_l=getattr(
                            self.cfg, "readout_block_l", None
                        ),
                    ),
                    cache=self.cache,
                )
                self._sthcs[key] = sthc
        return sthc

    # -- tenant management -------------------------------------------------

    def add_tenant(
        self,
        name: str,
        kernels: jax.Array | np.ndarray,
        fidelity: FidelityPipeline | None = None,
        slm: optics.SLMConfig | None = None,
        atoms: atomic.AtomicConfig | None = None,
    ) -> "VideoSearchServer":
        """Register a reference kernel set and record it into the cache.

        ``fidelity`` selects this kernel set's physics pipeline (None =
        the server default): tenants at different fidelities coexist on
        one server, one shared cache — the cache key's pipeline
        fingerprint keeps their gratings apart.

        ``slm`` / ``atoms`` give the tenant its own device model (None =
        the server default): the tenant routes to an engine keyed on
        (fidelity fingerprint, device fingerprint) and its cache key
        carries both device configs, so tenants on different hardware
        never cross-hit — yet they still pool into one dispatch whenever
        their encode semantics (SLM bit depth) match, record-time device
        physics being baked into each grating.
        """
        kt = int(kernels.shape[-1])
        if self.cfg.window_frames <= kt - 1:
            raise ValueError(
                f"coherence window ({self.cfg.window_frames}) must be at "
                f"least the kernel length ({kt}) for tenant {name!r}"
            )
        kh, kw = int(kernels.shape[-3]), int(kernels.shape[-2])
        if kh > self.frame_hw[0] or kw > self.frame_hw[1]:
            # an oversized kernel would slip through to a negative valid
            # output shape and silently garbage correlation maps
            raise ValueError(
                f"kernel spatial size ({kh}x{kw}) exceeds the server frame "
                f"size ({self.frame_hw[0]}x{self.frame_hw[1]}) for tenant "
                f"{name!r}"
            )
        # hash the kernel bytes once here, not per query; keep the copy
        # host-side so per-tenant device residency isn't charged outside
        # the cache byte budget
        # np.array (not asarray): force a copy so a caller mutating its
        # buffer afterwards can't desync the stored bytes from the
        # content-hash key computed below
        kernels = np.array(kernels)
        pipe = fidelity if fidelity is not None else self._default_fidelity
        sthc = self._sthc_for(pipe, slm, atoms)
        signal_shape = self._signal_shape()
        # the key carries this tenant's pipeline fingerprint *and* the
        # resolved device configs: identical kernel bytes under another
        # fidelity or device model hash to a different entry
        key = GratingCache.key_for(kernels, signal_shape, sthc.config)
        r_slm, r_atoms = self._resolve_device(slm, atoms)
        device_label = (
            "default"
            if slm is None and atoms is None
            else f"slm(bits={r_slm.bits})/atoms({r_atoms.ihb_profile},"
            f"t2={r_atoms.t2_s:g}s)"
        )
        ten = _Tenant(
            kernels=kernels,
            kt=kt,
            channels=int(kernels.shape[1]),
            signal_shape=signal_shape,
            key=key,
            sthc=sthc,
            fidelity_label=pipe.describe(),
            device_label=device_label,
        )
        with self._lock:
            old = self._tenants.pop(name, None)
            self._tenants[name] = ten
            if old is not None:
                # replacing a name must not leak the old grating — but
                # keys are content-addressed, so only drop it when no
                # surviving tenant shares the same kernel bytes
                self._discard_if_unreferenced(old.key)
                self._retire(old)
        # warm the shared cache (may evict LRU peers); recorded off the
        # local tenant object so a racing remove_tenant(name) can't
        # invalidate the lookup mid-warm
        self._fetch_grating(name, ten)
        return self

    # The serving-API name for tenant registration: a tenant *is* a named
    # kernel set (+ its fidelity pipeline) recorded into the shared cache.
    add_kernel_set = add_tenant

    def remove_tenant(self, name: str) -> None:
        """Drop a tenant; free its grating unless another tenant (with
        byte-identical kernels) still references the shared entry."""
        with self._lock:
            if name not in self._tenants:
                raise KeyError(
                    f"unknown tenant {name!r}; have {list(self._tenants)}"
                )
            ten = self._tenants.pop(name)
            self._discard_if_unreferenced(ten.key)
            self._retire(ten)

    def _retire(self, ten: _Tenant) -> None:  # holds-lock: _lock
        # fold a departing tenant's traffic into the server-wide totals
        # so metrics() rates don't rewind
        self._retired.queries += ten.queries
        self._retired.windows += ten.windows
        self._retired.frames += ten.frames
        self._retired.seconds += ten.seconds

    def _discard_if_unreferenced(self, key: tuple | None) -> None:  # holds-lock: _lock
        if key is not None and all(
            t.key != key for t in self._tenants.values()
        ):
            self.cache.discard(key)

    @property
    def tenants(self) -> list[str]:
        return list(self._tenants)

    def _signal_shape(self) -> tuple[int, int, int]:
        return (self.frame_hw[0], self.frame_hw[1], self.cfg.window_frames)

    def _grating(self, name: str):
        return self._fetch_grating(name, self._tenants[name])

    def _fetch_grating(self, name: str, ten: _Tenant):
        """The one grating-fetch path (warm-up and queries): hit while
        resident, re-record on miss.  If ``name`` was removed/replaced
        while we recorded, drop the now-unreferenced entry — a raced
        fetch must not leave an orphan grating charged against the
        shared LRU budget."""
        if self.chaos is not None:
            self.chaos.on("cache_fetch")
        grating = self.cache.get_or_record(
            ten.sthc.engine,  # the tenant's own-fidelity engine
            ten.kernels,
            # re-record at the geometry the key was hashed for, not the
            # live (mutable) cfg's current value
            ten.signal_shape or self._signal_shape(),
            key=ten.key,
            # checked under the *cache* lock just before insertion, so a
            # record in flight for a just-removed tenant never evicts
            # live peers to cache itself; deliberately lock-free (taking
            # self._lock there would invert the server->cache lock order)
            admit=lambda: self._tenants.get(name) is ten,
        )
        with self._lock:
            if self._tenants.get(name) is not ten:
                # the admit check races removal by a hair: sweep any
                # entry that still slipped in
                self._discard_if_unreferenced(ten.key)
        return grating

    # -- query -------------------------------------------------------------

    def search(
        self,
        clip: jax.Array,
        tenant: str = "default",
        return_volume: bool = False,
    ) -> dict:
        """clip: (B, C, H, W, T) long stream.  Returns detections.

        Detection = per-kernel max correlation over space-time + argmax
        frame (the photon-echo peak position in the window).  One call
        is exactly a one-request ``search_batch`` — single-request and
        pooled entry points share every readout path, so scores are
        bitwise-identical across them.

        Raises :class:`TenantQuarantined` if the signal-integrity guard
        rejected this request's scores (see ``search_batch``).
        """
        (out,) = self.search_batch(
            [(tenant, clip)], return_volume=return_volume
        )
        if isinstance(out, ServingError):
            raise out
        return out

    def search_batch(
        self,
        requests: Sequence[tuple[str, jax.Array]],
        pooled: bool | None = None,
        clip_keys: Sequence[tuple | None] | None = None,
        dedup: bool | None = None,
        return_volume: bool = False,
    ) -> list[dict]:
        """Schedule concurrent stream searches.

        Requests — ``(tenant, clip)`` pairs — are grouped by tenant and
        stream shape; each tenant-group stacks on the batch axis.  With
        ``pooled`` (default ``cfg.pooled_queries``) all groups then go to
        the engine's cross-tenant executor in one call
        (``QueryEngine.query_stream_many``): tenants whose gratings
        share the window FFT geometry and encode semantics are served
        from one pooled arena — one FFT + pooled MAC + IFFT per window
        chunk for the *whole mixed-tenant batch* — and, with ``dedup``
        (default ``cfg.dedup_clips``), tenant-groups whose clips hash
        content-equal collapse onto one shared physical row (the
        shared-stream fan-out: one forward FFT for every tenant
        searching the same stream).  ``clip_keys`` lets the microbatch
        scheduler pass per-request content fingerprints hashed once at
        submit time (None = hashed here).  ``pooled=False`` is the
        per-tenant-sequential dispatch loop (one streaming correlation
        per tenant-group; the benchmark baseline).  Results come back
        in request order.

        With ``cfg.fused_readout`` (default on) the detection readout
        is fused into the engine's overlap-save epilogue: no
        correlation volume materializes — each dispatch returns only
        the per-(stream, kernel) top-K states, bitwise equal to
        reducing the stitched volume.  ``return_volume=True`` forces
        the stitched path for this call and adds each request's
        ``(B, O, H', W', T')`` feature-map slice to its result dict
        under ``"volume"`` (the equivalence oracle; also the debugging
        escape hatch).

        With ``cfg.guard_scores`` (default on) each request's scores
        are finite-checked before delivery: a NaN/Inf row yields a
        :class:`TenantQuarantined` *instance* in that request's result
        slot (row-level isolation — the other requests in the pooled
        batch deliver bitwise-identical to a fault-free run).  Callers
        going through :meth:`search` or the scheduler see it raised /
        set on the future; direct callers must check
        ``isinstance(out, ServingError)``.
        """
        if pooled is None:
            pooled = getattr(self.cfg, "pooled_queries", True)
        if dedup is None:
            dedup = getattr(self.cfg, "dedup_clips", True)
        fused = (
            getattr(self.cfg, "fused_readout", True) and not return_volume
        )
        topk = max(1, int(getattr(self.cfg, "readout_topk", 1)))
        groups: dict[tuple, list[int]] = {}
        with self._lock:  # snapshot: a racing remove_tenant can't break
            tenants = dict(self._tenants)
        for i, (tenant, clip) in enumerate(requests):
            if tenant not in tenants:
                raise KeyError(
                    f"unknown tenant {tenant!r}; have {list(tenants)}"
                )
            # validate geometry upfront too, so one bad request fails the
            # batch before any group has burned device time
            if tuple(clip.shape[-3:-1]) != self.frame_hw:
                raise ValueError(
                    f"request {i}: clip frames {clip.shape[-3:-1]} do not "
                    f"match the server frame size {self.frame_hw}"
                )
            if clip.shape[-1] < tenants[tenant].kt:
                raise ValueError(
                    f"request {i}: stream of {clip.shape[-1]} frames is "
                    f"shorter than tenant {tenant!r}'s kernel length "
                    f"({tenants[tenant].kt})"
                )
            if clip.shape[1] != tenants[tenant].channels:
                raise ValueError(
                    f"request {i}: clip has {clip.shape[1]} channels; "
                    f"tenant {tenant!r} was recorded with "
                    f"{tenants[tenant].channels}"
                )
            # dtype is part of the group key: stacking f32 with f64 would
            # silently promote and change the f32 requests' scores
            key = (tenant, clip.shape[1:], jnp.dtype(clip.dtype))
            groups.setdefault(key, []).append(i)

        # one stacked clip batch per tenant-group, in *canonical* group
        # order: the pooled executor bakes the batch composition into
        # its jitted trace, so permutations of the same tenant mix must
        # map to one composition, not one retrace each
        order = sorted(
            groups.items(), key=lambda kv: (kv[0][0], str(kv[0][1:]))
        )
        tens = [tenants[key[0]] for key, _ in order]
        stacks = [
            requests[idxs[0]][1]  # single request: no device copy
            if len(idxs) == 1
            else jnp.concatenate([requests[i][1] for i in idxs], axis=0)
            for _, idxs in order
        ]
        if self.chaos is not None:  # chaos seam: batch encode/stacking
            self.chaos.on("encode", mode="pooled" if pooled else "sequential")

        if pooled:
            # pooled cross-tenant dispatch: fetch all gratings, then one
            # engine call answers every same-geometry group together.
            # The pooled executor is fidelity-agnostic (record-time
            # physics is baked into each grating), so the server's
            # default engine serves all tenants' gratings.
            t0 = time.time()
            gratings = [
                self._fetch_grating(key[0], ten)
                for (key, _), ten in zip(order, tens)
            ]
            # per-group clip identities for the shared-stream dedup: a
            # stacked group's identity is the tuple of its members'
            # content hashes (hashed once per distinct array object —
            # or upstream at scheduler submit time, via ``clip_keys``)
            group_keys = None
            if dedup:
                if clip_keys is None:
                    clip_keys = clip_keys_for([clip for _, clip in requests])
                group_keys = []
                for _, idxs in order:
                    ks = [clip_keys[i] for i in idxs]
                    if any(k is None for k in ks):
                        group_keys.append(None)
                    elif len(ks) == 1:
                        group_keys.append(ks[0])
                    else:
                        group_keys.append(("stack",) + tuple(ks))
            if self.chaos is not None:  # chaos seam: pooled dispatch
                self.chaos.on("dispatch", mode="pooled")
            if fused:
                # fused readout: the pooled dispatch itself returns the
                # per-request top-K states — no volume, no separate
                # readout launch
                fmaps = None
                dets = self.sthc.engine.query_stream_many(
                    list(zip(gratings, stacks)),
                    clip_keys=group_keys,
                    dedup=dedup,
                    readout_k=topk,
                    mesh=self.mesh,
                )
                jax.block_until_ready(
                    tuple((d.scores, d.index) for d in dets)
                )
            else:
                dets = None
                fmaps = self.sthc.engine.query_stream_many(
                    list(zip(gratings, stacks)),
                    clip_keys=group_keys,
                    dedup=dedup,
                    mesh=self.mesh,
                )
                # stitched detection readout rides the batch too: one
                # jitted call for every group's peak + argmax instead of
                # an eager op chain (with its host sync) per tenant
                readouts = self._readout(tuple(fmaps))
                readouts = jax.block_until_ready(readouts)
            dt = time.time() - t0
            with self._lock:
                self._pooled_dispatches += 1
            lat = [dt] * len(order)  # every request rode the one dispatch
            # credit the tenant busy-seconds proportionally to each
            # group's window share: the batch paid dt *once*, and the
            # windows/s rate must not divide by dt × n_groups
            plans = [
                ten.sthc.engine.stream_plan_for(g, clips.shape[-1])
                for ten, g, clips in zip(tens, gratings, stacks)
            ]
            weights = [
                p.n_blocks * int(clips.shape[0])
                for p, clips in zip(plans, stacks)
            ]
            total_w = sum(weights) or 1
            busy = [dt * w / total_w for w in weights]
        else:
            gratings, plans, lat, busy = [], [], [], []
            fmaps = None if fused else []
            dets = [] if fused else None
            for (key, idxs), ten, clips in zip(order, tens, stacks):
                t0 = time.time()
                grating = self._fetch_grating(key[0], ten)
                if self.chaos is not None:  # chaos seam: sequential path
                    self.chaos.on("dispatch", mode="sequential")
                if fused:
                    det = ten.sthc.engine.query_stream(
                        grating, clips, readout_k=topk
                    )
                    jax.block_until_ready((det.scores, det.index))
                    dets.append(det)
                else:
                    fmap = ten.sthc.engine.query_stream(grating, clips)
                    # honest serving latency
                    fmap = jax.block_until_ready(fmap)
                    fmaps.append(fmap)
                dt = time.time() - t0
                with self._lock:
                    self._sequential_dispatches += 1
                gratings.append(grating)
                # the exact plan the correlation ran under (derived from
                # the grating's recorded geometry, not the live cfg)
                plans.append(
                    ten.sthc.engine.stream_plan_for(grating, clips.shape[-1])
                )
                lat.append(dt)
                busy.append(dt)
            if not fused:
                # same shared readout helper as the pooled path (one
                # jitted call; bitwise-identical scores across entry
                # points), timed outside the per-group latency windows
                readouts = jax.block_until_ready(
                    self._readout(tuple(fmaps))
                )

        results: list[dict | None] = [None] * len(requests)
        with self._lock:
            for g_i, ((key, idxs), ten, clips) in enumerate(
                zip(order, tens, stacks)
            ):
                # the snapshot tenant may have been removed/retired during
                # the correlation — credit its traffic to the server-wide
                # totals instead so metrics() never undercounts
                tgt = (
                    ten
                    if self._tenants.get(key[0]) is ten
                    else self._retired
                )
                n_streams = clips.shape[0]
                tgt.queries += len(idxs)
                tgt.windows += plans[g_i].n_blocks * n_streams
                tgt.frames += int(clips.shape[-1]) * n_streams
                tgt.seconds += busy[g_i]
        guard = getattr(self.cfg, "guard_scores", True)
        for g_i, ((key, idxs), clips) in enumerate(zip(order, stacks)):
            tenant = key[0]
            plan = plans[g_i]
            topk_s = topk_t = None
            if fused:
                # fused readout: slot 0 of the (B, O, K) state IS the
                # stitched max/argmax (total selection order, k=1 ==
                # first-occurrence argmax); tmod comes off the state's
                # recorded valid-T extent — no volume anywhere
                det = dets[g_i]
                tmod = int(det.out_shape[-1])
                # transfer the tiny (B, O, K) state once and slice on
                # the host — a device-side [..., 0] would be one more
                # dispatch per request on the hot path
                state_s = np.asarray(det.scores)
                state_i = np.asarray(det.index)
                peak = state_s[..., 0]
                idx = state_i[..., 0]
                if topk > 1:
                    topk_s = state_s
                    ti = state_i
                    # exhausted slots carry the empty sentinel: report
                    # frame −1 rather than a garbage modulo
                    topk_t = np.where(
                        ti == TOPK_EMPTY_IDX, -1, ti % tmod
                    )
            else:
                tmod = int(fmaps[g_i].shape[-1])
                peak = np.asarray(readouts[g_i][0])
                idx = np.asarray(readouts[g_i][1])
            if self.chaos is not None:  # chaos seam: detection readout
                peak = self.chaos.on(
                    "readout",
                    mode="pooled" if pooled else "sequential",
                    payload=peak,
                )
            t_idx = idx % tmod
            b = 0
            for i in idxs:
                nb = requests[i][1].shape[0]
                scores = peak[b : b + nb]
                # signal-integrity guard on the already-host-resident
                # peaks: one NaN/Inf row quarantines one request, the
                # rest of the pooled batch delivers untouched (a NaN in
                # a fused row propagates into its peak slot, so the
                # check is path-independent)
                if guard and not np.isfinite(scores).all():
                    with self._lock:
                        self._quarantined += 1
                    results[i] = TenantQuarantined(  # type: ignore[call-overload]
                        f"non-finite correlation scores for tenant "
                        f"{tenant!r}; request quarantined",
                        tenant=tenant,
                    )
                else:
                    res = {
                        "tenant": tenant,
                        "scores": scores,
                        "peak_frame": t_idx[b : b + nb],
                        "latency_s": lat[g_i],
                        "windows": plan.n_blocks,
                    }
                    if topk_s is not None:
                        res["topk_scores"] = topk_s[b : b + nb]
                        res["topk_frames"] = topk_t[b : b + nb]
                    if return_volume:
                        res["volume"] = fmaps[g_i][b : b + nb]
                    results[i] = res
                b += nb
        return results  # type: ignore[return-value]

    # -- observability -----------------------------------------------------

    def metrics(self) -> dict:
        """Serving metrics: cache counters + measured vs projected rates.

        Rates divide by summed per-group *busy* seconds, not elapsed
        wall time — with searches running concurrently from several
        threads the overlapping intervals double-count and the reported
        frames/s / windows/s are a lower bound on the true rate.
        """
        with self._lock:
            per_tenant = {
                name: {
                    "fidelity": t.fidelity_label,
                    "device": t.device_label,
                    "queries": t.queries,
                    "windows": t.windows,
                    "frames": t.frames,
                    "seconds": t.seconds,
                }
                for name, t in self._tenants.items()
            }
            retired = self._retired
            queries = retired.queries + sum(
                t["queries"] for t in per_tenant.values()
            )
            windows = retired.windows + sum(
                t["windows"] for t in per_tenant.values()
            )
            frames = retired.frames + sum(
                t["frames"] for t in per_tenant.values()
            )
            seconds = retired.seconds + sum(
                t["seconds"] for t in per_tenant.values()
            )
        fps = frames / seconds if seconds > 0 else 0.0
        with self._lock:
            pooled = self._pooled_dispatches
            sequential = self._sequential_dispatches
        with self._lock:
            quarantined = self._quarantined
        return {
            "cache": self.cache.stats(),
            "tenants": per_tenant,
            "pooled_dispatches": pooled,
            "sequential_dispatches": sequential,
            # intra-replica device mesh (None = single-device serving)
            "mesh": (
                {
                    "shape": dict(self.mesh.shape),
                    "devices": self.mesh.size,
                }
                if self.mesh is not None
                else None
            ),
            # requests the signal-integrity guard isolated (NaN/Inf rows)
            "quarantined": quarantined,
            # shared-stream fan-out: clip rows the pooled executor
            # collapsed onto shared physical rows (one FFT per stream,
            # not per request)
            "dedup": self.sthc.engine.pool_stats(),
            "queries": queries,
            "windows_total": windows,
            "frames_total": frames,
            "windows_per_s": windows / seconds if seconds > 0 else 0.0,
            "frames_per_s": fps,
            # measured digital-twin rate vs the paper's projected loaders
            "projected_slm_fps": throughput.SLM_FPS,
            "projected_hmd_fps": throughput.HMD_FPS,
            "frames_per_s_vs_slm": fps / throughput.SLM_FPS,
            "frames_per_s_vs_hmd": fps / throughput.HMD_FPS,
        }


# ---------------------------------------------------------------------------
# Async microbatch scheduling (queue → batcher → pooled executor)
# ---------------------------------------------------------------------------


# RequestRejected (and the rest of the typed ServingError taxonomy) now
# lives in repro.launch.resilience; re-imported above so existing
# ``from repro.launch.serve import RequestRejected`` callers keep working.


@dataclasses.dataclass(eq=False)  # identity semantics: the clip field
class _Pending:  # would make field-wise == ambiguous (array truthiness)
    tenant: str
    clip: jax.Array
    future: Future
    t_submit: float
    # content fingerprint of the clip, hashed once in the submitter's
    # thread (off the batcher's critical path) — the identity the
    # shared-stream dedup groups ride on
    clip_id: tuple | None = None
    # absolute wall-clock deadline (time.time() frame); None = none
    deadline: float | None = None


class MicrobatchScheduler:
    """Async microbatch front end for a :class:`VideoSearchServer`.

    The queue stage of the serving architecture (see the module
    docstring): callers ``submit()`` requests and get a
    :class:`concurrent.futures.Future`; a scheduler thread drains the
    bounded queue into mixed-tenant microbatches and dispatches each
    through ``server.search_batch`` — where same-geometry tenants pool
    into single device dispatches.

    * **Admission control / backpressure** — the queue holds at most
      ``max_queue`` requests.  ``submit(block=False)`` (default) sheds
      immediately on a full queue: the request never occupies device
      time, the ``rejected`` counter increments, and the caller gets
      :class:`RequestRejected` to degrade/retry against.
      ``submit(block=True)`` instead blocks the caller until the queue
      drains — backpressure for loaders that must not drop work.
    * **Batch forming** — the scheduler takes the first queued request,
      then waits up to ``batch_wait_s`` for more, collecting up to
      ``max_batch`` requests of the *same clip shape* (requests of other
      shapes are stashed for the next cycle, preserving arrival order
      within a shape).  Tenants mix freely inside a batch — that is the
      point: the pooled executor serves them in one dispatch.
    * **Observability** — per-request end-to-end latency (submit →
      result) is recorded in a sliding window; :meth:`metrics` reports
      p50/p90/p99 alongside queue depth, shed/submit/complete counters
      and the mean formed batch size.
    * **Resilience** (see the module docstring's *Failure semantics*) —
      deadlines (``default_deadline_s`` / per-request ``deadline_s``)
      enforced at dispatch, across retries, and by a watchdog thread
      that resolves any overdue future with ``DeadlineExceeded``;
      transient dispatch failures retried under a seeded decorrelated-
      jitter ``RetryPolicy``; repeated failures trip the
      ``DegradationLadder``'s per-mode circuit breakers, degrading
      pooled → sequential → single-request dispatch and recovering via
      half-open probes.  (The ``pooled`` rung honors the server's
      ``cfg.pooled_queries`` — it is "the server's preferred path", not
      an override.)  Every future resolves with a result or a typed
      ``ServingError``; queued futures are resolved with
      ``SchedulerClosed`` on shutdown.

    Use as a context manager or call :meth:`close` — pending futures are
    failed (never left hanging) on shutdown.
    """

    def __init__(
        self,
        server: VideoSearchServer,
        max_queue: int = 64,
        max_batch: int = 8,
        batch_wait_s: float = 0.002,
        latency_window: int = 1024,
        default_deadline_s: float | None = None,
        retry: RetryPolicy | None = None,
        ladder: DegradationLadder | None = None,
        watchdog_interval_s: float = 0.02,
    ):
        if max_queue < 1 or max_batch < 1:
            raise ValueError("max_queue and max_batch must be >= 1")
        self.server = server
        self.max_batch = int(max_batch)
        self.batch_wait_s = float(batch_wait_s)
        self.default_deadline_s = default_deadline_s
        self.retry = retry if retry is not None else RetryPolicy()
        self.ladder = ladder if ladder is not None else DegradationLadder()
        self._q: queue_mod.Queue[_Pending] = queue_mod.Queue(maxsize=max_queue)
        # batcher-thread only (and _drain_and_fail, which runs strictly
        # after the batcher thread is dead) — deliberately unguarded
        self._stash: collections.deque[_Pending] = collections.deque()
        self._lock = threading.Lock()
        self._latencies: collections.deque[float] = collections.deque(  # guarded-by: _lock
            maxlen=latency_window
        )
        self._batch_sizes: collections.deque[int] = collections.deque(  # guarded-by: _lock
            maxlen=latency_window
        )
        self.submitted = 0  # guarded-by: _lock
        self.completed = 0  # guarded-by: _lock
        self.rejected = 0  # guarded-by: _lock
        self.failed = 0  # guarded-by: _lock
        self.batches = 0  # guarded-by: _lock
        # requests that joined an existing shared-stream dedup group
        # (same-clip rows beyond the first in a formed batch)
        self.dedup_grouped = 0  # guarded-by: _lock
        self.deadline_missed = 0  # guarded-by: _lock
        self.retries = 0  # guarded-by: _lock
        self.quarantined = 0  # guarded-by: _lock
        self._batch_seq = 0  # guarded-by: _lock
        # serializes intake against close(): submit must never land a
        # request after close() drained the queue (its future would hang
        # forever).  Deliberately NOT self._lock — the batcher takes
        # that inside _dispatch, and a submitter blocked on a full
        # queue while holding it would deadlock the drain.
        self._intake_lock = threading.Lock()
        self._closed = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="sthc-microbatcher", daemon=True
        )
        self._thread.start()
        # the no-hangs backstop: resolves overdue futures with
        # DeadlineExceeded and fails everything if the batcher dies
        self._watchdog = Watchdog(
            interval_s=watchdog_interval_s,
            on_expire=self._on_deadline_expired,
            on_tick=self._check_liveness,
        )

    # -- intake ------------------------------------------------------------

    def submit(
        self,
        tenant: str,
        clip: jax.Array,
        block: bool = False,
        deadline_s: float | None = None,
    ) -> Future:
        """Enqueue one search; returns a future resolving to the same
        result dict ``search_batch`` produces (plus ``queue_latency_s``,
        the end-to-end submit→result time).  The clip's content
        fingerprint is hashed here, in the caller's thread, so the
        batcher can form shared-stream dedup groups without re-reading
        clip bytes — skipped entirely when the server's dedup is off
        (the fingerprint would be discarded; no point paying a full
        host copy + SHA-1 per request for it).

        ``deadline_s`` (default ``self.default_deadline_s``; None = no
        deadline) bounds submit → result: past it the future resolves
        with :class:`DeadlineExceeded` — enforced at dispatch, across
        retries, and by the watchdog thread as the backstop."""
        cfg = self.server.cfg
        wants_dedup = getattr(cfg, "dedup_clips", True) and getattr(
            cfg, "pooled_queries", True
        )  # the sequential executor never reads clip keys either
        cid = clip_key(clip) if wants_dedup else None
        now = time.time()
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        deadline = now + deadline_s if deadline_s is not None else None
        item = _Pending(tenant, clip, Future(), now, cid, deadline)
        # every put happens under the intake lock (so close() can never
        # miss a request and leave its future hanging), but the lock is
        # never *held across a blocking wait*: a backpressured
        # block=True submitter polls for a slot between acquisitions,
        # so shed-immediately submitters and close() stay responsive.
        while True:
            with self._intake_lock:
                if self._closed.is_set():
                    raise SchedulerClosed("scheduler is closed")
                try:
                    self._q.put_nowait(item)
                    break
                except queue_mod.Full:
                    if not block:
                        with self._lock:
                            self.rejected += 1
                        raise RequestRejected(
                            f"request queue full ({self._q.maxsize} deep); "
                            f"request for tenant {tenant!r} shed",
                            tenant=tenant,
                        ) from None
            time.sleep(0.001)  # backpressure: wait for a slot
        with self._lock:
            self.submitted += 1
        self._watchdog.track(item.future, deadline, tenant)
        return item.future

    def search(self, tenant: str, clip: jax.Array, block: bool = True) -> dict:
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(tenant, clip, block=block).result()

    # -- the batcher loop --------------------------------------------------

    def _take(self, timeout: float) -> _Pending | None:
        if self._stash:
            return self._stash.popleft()
        try:
            return self._q.get(timeout=timeout)
        except queue_mod.Empty:
            return None

    def _run(self) -> None:
        while True:
            if self._closed.is_set():
                # exit promptly: anything still queued/stashed is failed
                # by close()'s drain — shutdown must not first serve an
                # arbitrarily deep backlog
                return
            item = self._take(timeout=0.05)
            if item is None:
                continue
            batch = [item]
            shape = tuple(item.clip.shape)
            deadline = item.t_submit + self.batch_wait_s
            # coalesce with earlier same-shape stash leftovers first —
            # requests deferred by a shape mismatch must still get the
            # pooled dispatch they waited for
            kept: collections.deque[_Pending] = collections.deque()
            while self._stash and len(batch) < self.max_batch:
                nxt = self._stash.popleft()
                if tuple(nxt.clip.shape) == shape:
                    batch.append(nxt)
                else:
                    kept.append(nxt)
            kept.extend(self._stash)
            self._stash = kept
            # then the live queue: wait out the deadline for a fuller
            # batch, and past it take only what is already here —
            # bounded to max_batch pulls per cycle, so a sustained
            # other-shape stream can neither livelock this batch nor
            # grow the stash without bound (admission control stays
            # with the queue)
            skipped: list[_Pending] = []
            while (
                len(batch) < self.max_batch
                and len(batch) + len(skipped) < 2 * self.max_batch
            ):
                rem = deadline - time.time()
                try:
                    if rem > 0:
                        nxt = self._q.get(timeout=rem)
                    else:
                        nxt = self._q.get_nowait()
                except queue_mod.Empty:
                    break
                # batches form across tenants but per clip shape: the
                # pooled executor groups by geometry anyway, and keeping
                # one shape per microbatch keeps its dispatch singular
                if tuple(nxt.clip.shape) == shape:
                    batch.append(nxt)
                else:
                    skipped.append(nxt)
            self._stash.extend(skipped)  # next cycle, arrival order kept
            try:
                self._dispatch(self._form_dedup_groups(batch))
            except Exception:  # noqa: BLE001 — the batcher must survive
                # _dispatch fails futures itself; this is a belt for
                # future-state races etc. — a dead batcher thread would
                # hang every subsequent request
                pass

    def _form_dedup_groups(self, batch: list[_Pending]) -> list[_Pending]:
        """Arrange a formed microbatch into shared-stream dedup groups:
        requests whose clips hash content-equal become adjacent (stable
        within a group, groups in first-arrival order), so the pooled
        executor's row collapse is visible in the batch layout.  Rows
        the dedup will collapse (every request beyond the first of its
        clip) are counted for :meth:`metrics`."""
        groups: dict[tuple, list[_Pending]] = {}
        singles: list[_Pending] = []  # unhashable clips: never deduped
        order: list[tuple] = []  # first-arrival group order
        for p in batch:
            if p.clip_id is None:
                singles.append(p)
                continue
            if p.clip_id not in groups:
                order.append(p.clip_id)
            groups.setdefault(p.clip_id, []).append(p)
        shared = sum(len(g) - 1 for g in groups.values())
        if shared:
            with self._lock:
                self.dedup_grouped += shared
        return [p for k in order for p in groups[k]] + singles

    @staticmethod
    def _claim(future: Future) -> bool:
        """``set_running_or_notify_cancel`` tolerant of the watchdog
        having already resolved the future (raises from FINISHED)."""
        try:
            return future.set_running_or_notify_cancel()
        except Exception:  # noqa: BLE001 — InvalidStateError
            return False

    def _expire(self, p: _Pending, batch_id: int | None) -> None:
        if resolve_exception(
            p.future,
            DeadlineExceeded(
                f"deadline passed before dispatch for tenant {p.tenant!r}",
                tenant=p.tenant,
                batch_id=batch_id,
            ),
        ):
            with self._lock:
                self.deadline_missed += 1
                self.failed += 1

    def _dispatch(self, batch: list[_Pending]) -> None:
        # claim each future before any work: a caller may have
        # cancel()led a pending one, and set_result on a cancelled
        # future raises (killing the batcher); claiming also locks out
        # late cancels during the server call.  _execute below assumes
        # every future it sees is already claimed (the singles retry
        # path must not re-claim).
        # repro-lint LD202: _batch_seq is written by the batcher thread
        # only today, but metrics()/debugging read it concurrently and
        # nothing structural stops a second dispatcher — take the counter
        # lock like every other counter rather than rely on the comment.
        with self._lock:
            self._batch_seq += 1
            batch_id = self._batch_seq
        batch = [p for p in batch if self._claim(p.future)]
        if batch:
            self._execute(batch, batch_id)

    def _run_mode(self, mode: str, batch: list[_Pending]) -> list:
        """One dispatch in the given ladder mode.  ``pooled`` defers to
        the server's configured preference (``cfg.pooled_queries``);
        ``sequential`` forces the per-tenant dispatch loop; ``single``
        additionally drops microbatching — one server call per request,
        the floor the ladder can always serve from."""
        keys = [p.clip_id for p in batch]
        reqs = [(p.tenant, p.clip) for p in batch]
        if mode == "pooled":
            # fingerprints were hashed at submit: the executor's dedup
            # must not re-read the clip bytes per batch
            return self.server.search_batch(reqs, clip_keys=keys)
        if mode == "sequential":
            return self.server.search_batch(reqs, pooled=False, clip_keys=keys)
        outs = []
        for req, key in zip(reqs, keys):
            outs.extend(
                self.server.search_batch([req], pooled=False, clip_keys=[key])
            )
        return outs

    def _execute(self, batch: list[_Pending], batch_id: int) -> None:
        """Serve one claimed microbatch to completion: ladder-mode
        selection, transient-failure retries under the seeded backoff,
        deadline pruning between attempts, and typed-error resolution.
        Every future in ``batch`` is resolved by the time this returns
        (or already was, by the watchdog/close)."""
        # retry truncation: the schedule ends once a sleep would run past
        # the batch's earliest request deadline — sleeping into a
        # guaranteed DeadlineExceeded wastes the budget's tail.  The min
        # over the formed batch is conservative for later-deadline peers
        # (they ride the same dispatch anyway).
        deadlines = [p.deadline for p in batch if p.deadline is not None]
        delays = self.retry.delays(
            deadline=min(deadlines) if deadlines else None
        )
        while True:
            now = time.time()
            live: list[_Pending] = []
            for p in batch:
                if p.future.done():  # watchdog/cancel won the race
                    continue
                if p.deadline is not None and now >= p.deadline:
                    self._expire(p, batch_id)
                    continue
                live.append(p)
            if not live:
                return
            batch = live
            mode = self.ladder.select()
            try:
                outs = self._run_mode(mode, batch)
            except Exception as exc:  # noqa: BLE001 — routed into futures
                # validation errors neither trip breakers nor retry: a
                # malformed request fails every rung identically
                if not is_validation_error(exc):
                    self.ladder.report(mode, ok=False)
                    if self.ladder.peek() != mode:
                        # the ladder degraded under us: re-dispatch on the
                        # lower rung — degradation is not a retry and must
                        # not consume the backoff budget
                        continue
                    if is_transient(exc):
                        delay = next(delays, None)
                        if delay is not None:
                            with self._lock:
                                self.retries += 1
                            time.sleep(delay)
                            continue
                if len(batch) > 1:
                    # one bad request fails the batched call upfront (the
                    # server validates before any device work): retry
                    # singly so the good requests still complete
                    for p in batch:
                        self._execute([p], batch_id)
                    return
                p = batch[0]
                if isinstance(exc, ServingError) or is_validation_error(exc):
                    err: BaseException = exc  # typed/caller error: as-is
                else:
                    err = BatchExecutionError(
                        f"batch {batch_id} failed in {mode!r} mode after "
                        f"retries: {exc}",
                        tenant=p.tenant,
                        batch_id=batch_id,
                    )
                    err.__cause__ = exc
                if resolve_exception(p.future, err):
                    with self._lock:
                        self.failed += 1
                return
            self.ladder.report(mode, ok=True)
            self._deliver(batch, outs, batch_id)
            return

    def _deliver(
        self, batch: list[_Pending], outs: list, batch_id: int
    ) -> None:
        now = time.time()
        with self._lock:
            self.batches += 1
            self._batch_sizes.append(len(batch))
        for p, out in zip(batch, outs):
            if isinstance(out, ServingError):
                # signal-integrity quarantine: the server isolated this
                # row; the rest of the batch delivered untouched
                out.tenant = out.tenant or p.tenant
                out.batch_id = batch_id
                if resolve_exception(p.future, out):
                    with self._lock:
                        self.quarantined += 1
                        self.failed += 1
                continue
            out["queue_latency_s"] = now - p.t_submit
            if resolve_result(p.future, out):
                with self._lock:
                    self.completed += 1
                    self._latencies.append(now - p.t_submit)

    # -- lifecycle / observability ----------------------------------------

    def _on_deadline_expired(self, tenant: str | None) -> None:
        # watchdog resolved an overdue future with DeadlineExceeded
        with self._lock:
            self.deadline_missed += 1
            self.failed += 1

    def _check_liveness(self) -> None:
        # watchdog tick: a dead batcher thread would hang every queued
        # future — close intake and resolve the backlog instead.  The
        # batcher loop swallows everything, so this is a pure backstop.
        if self._closed.is_set() or self._thread.is_alive():
            return
        with self._intake_lock:
            if self._closed.is_set():
                return
            self._closed.set()
        self._drain_and_fail(
            lambda p: BatchExecutionError(
                "scheduler batcher thread died", tenant=p.tenant
            )
        )

    def _drain_and_fail(self, make_exc) -> None:
        """Resolve everything still queued/stashed with ``make_exc(p)``."""
        leftovers = list(self._stash)
        self._stash.clear()
        while True:
            try:
                leftovers.append(self._q.get_nowait())
            except queue_mod.Empty:
                break
        for p in leftovers:
            if resolve_exception(p.future, make_exc(p)):
                with self._lock:
                    self.failed += 1

    def close(self) -> None:
        """Stop the batcher; resolve anything still queued with
        :class:`SchedulerClosed` (futures are never abandoned)."""
        with self._intake_lock:
            # under the intake lock: a submit() that already passed the
            # closed check finishes its put before we proceed, so no
            # request can land after the drain below and hang forever
            if self._closed.is_set():
                self._watchdog.close()
                return
            self._closed.set()
        self._thread.join()
        self._drain_and_fail(
            lambda p: SchedulerClosed("scheduler closed", tenant=p.tenant)
        )
        self._watchdog.close()

    def __enter__(self) -> "MicrobatchScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def metrics(self) -> dict:
        """Scheduler counters + end-to-end latency percentiles +
        resilience state (ladder mode, breaker snapshots, deadline/
        retry/quarantine counters)."""
        with self._lock:
            lats = sorted(self._latencies)
            sizes = list(self._batch_sizes)
            out = {
                "queue_depth": self._q.qsize() + len(self._stash),
                "max_queue": self._q.maxsize,
                "submitted": self.submitted,
                "completed": self.completed,
                "rejected": self.rejected,
                "failed": self.failed,
                "batches": self.batches,
                "dedup_grouped": self.dedup_grouped,
                "mean_batch_size": (
                    sum(sizes) / len(sizes) if sizes else 0.0
                ),
                "mode": self.ladder.peek(),
                "ladder": self.ladder.metrics(),
                "deadline_missed": self.deadline_missed,
                "retries": self.retries,
                "quarantined": self.quarantined,
                "watchdog_expired": self._watchdog.expired,
                "default_deadline_s": self.default_deadline_s,
            }
        for name, q in (("p50", 0.50), ("p90", 0.90), ("p99", 0.99)):
            out[f"latency_{name}_ms"] = (
                1e3 * lats[min(int(q * len(lats)), len(lats) - 1)]
                if lats
                else 0.0
            )
        return out


# ---------------------------------------------------------------------------
# Hybrid classifier serving (paper §4: conv optical, head digital)
# ---------------------------------------------------------------------------


class HybridClassifierServer:
    """Serve the trained hybrid 3-D CNN with the STHC conv backend."""

    def __init__(self, params: PyTree, cfg: hybrid.HybridConfig,
                 physical: bool = True,
                 fidelity: FidelityPipeline | None = None):
        self.cfg = cfg
        if fidelity is None:
            fidelity = (
                fidelity_mod.physical() if physical else fidelity_mod.ideal()
            )
        self.sthc = STHC(STHCConfig(fidelity=fidelity))
        # record once: the kernels live in the atomic medium
        self.grating = self.sthc.record(
            params["conv_w"], (cfg.height, cfg.width, cfg.frames)
        )
        self.params = params
        self._head = jax.jit(self._head_impl)

    def _head_impl(self, conv_out: jax.Array) -> jax.Array:
        p, cfg = self.params, self.cfg
        y = conv_out + p["conv_b"][None, :, None, None, None]
        y = jax.nn.relu(y)
        y = hybrid.max_pool3d(y, cfg.pool_window)
        y = y.reshape(y.shape[0], -1)
        y = jax.nn.relu(y @ p["fc1_w"] + p["fc1_b"][None, :])
        return y @ p["fc2_w"] + p["fc2_b"][None, :]

    def logits(self, clips: jax.Array) -> jax.Array:
        """(B, num_classes) logits of ``(B, C, H, W, T)`` clips."""
        conv = self.sthc.correlate(self.grating, clips)  # optical layer
        return self._head(conv)  # digital layers

    def classify(self, clips: jax.Array) -> np.ndarray:
        return np.asarray(jnp.argmax(self.logits(clips), axis=-1))

    def classify_stream(
        self, clips: jax.Array, block_t: int | None = None
    ) -> np.ndarray:
        """Long-clip inference (paper Fig. 1C): conv streams through the
        engine's coherence-window overlap-save path, then the digital
        head classifies each ``cfg.frames``-long segment of the stream.

        ``clips`` is (B, C, H, W, T) with arbitrary T ≥ ``cfg.frames``;
        returns (B, n_segments) class predictions, one per training-
        length window at stride ``ot = frames − k_t + 1`` (consecutive
        input windows overlap by k_t − 1 frames; their *conv outputs*
        tile the stream disjointly).  Segment s of the streamed conv
        output is exactly the one-shot conv of input frames
        ``[s·ot, s·ot + cfg.frames)``, so each prediction matches
        `classify` on that sub-clip (physical mode differs only in the
        stream-global vs per-segment SLM scale).
        """
        cfg = self.cfg
        if clips.shape[-1] < cfg.frames:
            # reject before any device work: a T >= kt stream would
            # stream-correlate fine yet still yield zero segments
            raise ValueError(
                f"stream of {clips.shape[-1]} frames is shorter than one "
                f"classification window ({cfg.frames} frames)"
            )
        conv = self.sthc.correlate_stream(
            self.params["conv_w"],
            clips,
            cfg.frames if block_t is None else int(block_t),
        )
        ot = cfg.conv_out_shape[2]
        n_seg = conv.shape[-1] // ot
        # fold the equal-shape segments into the batch axis: one head
        # dispatch + one host transfer regardless of stream length
        segs = conv[..., : n_seg * ot].reshape(conv.shape[:-1] + (n_seg, ot))
        segs = jnp.moveaxis(segs, -2, 0)  # segment-major
        segs = segs.reshape((n_seg * conv.shape[0],) + conv.shape[1:-1] + (ot,))
        logits = self._head(segs)
        preds = jnp.argmax(logits, axis=-1).reshape(n_seg, -1)
        return np.asarray(preds.T)  # (B, n_seg)


# ---------------------------------------------------------------------------
# LM serving
# ---------------------------------------------------------------------------


class LMServer:
    def __init__(self, cfg, params: PyTree, max_len: int = 128):
        self.cfg = cfg
        self.mod = model_api.get_model(cfg)
        self.params = params
        self.max_len = max_len
        self._decode = jax.jit(
            lambda p, c, t: self.mod.decode_step(cfg, p, c, t),
            donate_argnums=(1,),
        )

    def generate(self, prompts: jax.Array, n_tokens: int) -> np.ndarray:
        """Greedy generation.  prompts: (B, S) int32."""
        logits, cache = self.mod.prefill(
            self.cfg, self.params, prompts, max_len=self.max_len
        )
        out = [jnp.argmax(logits, -1)[:, None]]
        for _ in range(n_tokens - 1):
            logits, cache = self._decode(self.params, cache, out[-1])
            out.append(jnp.argmax(logits, -1)[:, None])
        return np.asarray(jnp.concatenate(out, axis=1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["video", "lm"], default="video")
    ap.add_argument("--frames", type=int, default=256)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "video":
        rng = np.random.RandomState(0)
        server = VideoSearchServer(frame_hw=(24, 32))
        kernels = jnp.asarray(rng.randn(4, 1, 12, 16, 8).astype(np.float32))
        # two tenants, two fidelities, one server + one shared cache
        server.add_kernel_set("events-ideal", kernels)
        server.add_kernel_set(
            "events-physical", kernels, fidelity=fidelity_mod.physical()
        )
        clip = jnp.asarray(rng.rand(2, 1, 24, 32, args.frames).astype(np.float32))
        outs = server.search_batch(
            [("events-ideal", clip), ("events-physical", clip)]
        )
        for out in outs:
            fid = server.metrics()["tenants"][out["tenant"]]["fidelity"]
            print(
                f"[{out['tenant']} ({fid})] searched {args.frames} frames "
                f"in {out['windows']} coherence windows, "
                f"latency {out['latency_s']:.3f}s"
            )
            print("  scores:", np.round(out["scores"], 2))
        m = server.metrics()
        print(
            f"cache: {m['cache']['hits']} hits / {m['cache']['misses']} misses"
            f" / {m['cache']['evictions']} evictions, "
            f"{m['cache']['bytes']/1e6:.1f} MB resident; "
            f"{m['frames_per_s']:.0f} frames/s measured "
            f"(SLM projection {m['projected_slm_fps']:.0f} fps)"
        )
    else:
        cfg = configs.get_smoke_config("qwen2-1.5b")
        mod = model_api.get_model(cfg)
        params, _ = mod.init_params(cfg, jax.random.PRNGKey(0))
        server = LMServer(cfg, params)
        toks = jnp.asarray(np.arange(8, dtype=np.int32)[None] % cfg.vocab)
        out = server.generate(toks, 8)
        print("generated:", out)


if __name__ == "__main__":
    main()
