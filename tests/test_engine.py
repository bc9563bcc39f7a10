"""Fused spectral query engine: single-FFT dataflow, fused-vs-unfused
equivalence at paper geometry, grating cache semantics, stmul v2 vs the
v1 kernel / jnp oracle, and batched overlap-save equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spectral_conv as sc
from repro.core import fidelity as fid
from repro.core.engine import GratingCache, QueryEngine
from repro.core.sthc import STHC, STHCConfig
from repro.kernels.stmul import ops as stmul_ops, ref as stmul_ref


def _clips(rng, B=2, C=1, H=20, W=24, T=10):
    return jnp.asarray(rng.rand(B, C, H, W, T).astype(np.float32))


def _kernels(rng, O=3, C=1, kh=7, kw=9, kt=4):
    return jnp.asarray(rng.randn(O, C, kh, kw, kt).astype(np.float32))


# -- fused query ≡ unfused two-query reference --------------------------------


def test_fused_equals_unfused_reference(rng):
    x = _clips(rng)
    k = _kernels(rng)
    sthc = STHC(STHCConfig(fidelity=fid.physical()))
    grating = sthc.record(k, x.shape[-3:])
    y_fused = sthc.engine.query(grating, x)
    y_ref = sthc.engine.query_unfused(grating, x)
    rel = float(jnp.linalg.norm(y_fused - y_ref) / jnp.linalg.norm(y_ref))
    assert rel <= 1e-4, rel


def test_fused_equals_unfused_paper_geometry(rng):
    """Acceptance geometry: the paper's 30×40×8 kernels on 60×80×16 clips."""
    x = _clips(rng, B=1, H=60, W=80, T=16)
    k = _kernels(rng, O=9, kh=30, kw=40, kt=8)
    sthc = STHC(STHCConfig(fidelity=fid.physical()))
    grating = sthc.record(k, x.shape[-3:])
    y_fused = sthc.engine.query(grating, x)
    y_ref = sthc.engine.query_unfused(grating, x)
    rel = float(jnp.linalg.norm(y_fused - y_ref) / jnp.linalg.norm(y_ref))
    assert rel <= 1e-4, rel


def test_fused_pallas_path_matches(rng):
    x = _clips(rng)
    k = _kernels(rng)
    ref = STHC(STHCConfig(fidelity=fid.physical()))(k, x)
    got = STHC(STHCConfig(fidelity=fid.physical(), use_pallas=True))(k, x)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel <= 1e-4, rel


def test_ideal_fused_is_exact(rng):
    x = _clips(rng)
    k = _kernels(rng)
    y = STHC(STHCConfig(fidelity=fid.ideal()))(k, x)
    ref = sc.direct_correlate3d(x, k, "valid")
    np.testing.assert_allclose(y, ref, atol=1e-4 * float(jnp.max(jnp.abs(ref))))


# -- the dataflow claim itself: exactly one forward FFT per clip --------------


def _count_ffts(jaxpr, kind: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "fft" and eqn.params["fft_type"].name == kind:
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):  # ClosedJaxpr (e.g. pjit)
                n += _count_ffts(v.jaxpr, kind)
            elif hasattr(v, "eqns"):  # raw Jaxpr
                n += _count_ffts(v, kind)
    return n


def test_fused_physical_query_computes_one_forward_fft(rng):
    x = _clips(rng)
    k = _kernels(rng)
    sthc = STHC(STHCConfig(fidelity=fid.physical()))
    grating = sthc.record(k, x.shape[-3:])
    fused = jax.make_jaxpr(lambda x: sthc.engine.query(grating, x))(x)
    assert _count_ffts(fused.jaxpr, "RFFT") == 1
    assert _count_ffts(fused.jaxpr, "IRFFT") == 1
    unfused = jax.make_jaxpr(lambda x: sthc.engine.query_unfused(grating, x))(x)
    assert _count_ffts(unfused.jaxpr, "RFFT") == 2  # the cost being removed
    assert _count_ffts(unfused.jaxpr, "IRFFT") == 2


# -- grating cache -------------------------------------------------------------


def test_cache_hits_on_identical_kernels(rng):
    cache = GratingCache()
    x = _clips(rng)
    k = _kernels(rng)
    sthc = STHC(STHCConfig(fidelity=fid.physical()), cache=cache)
    y1 = sthc(k, x)
    y2 = sthc(k, x)
    assert cache.misses == 1 and cache.hits == 1
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # same bytes in a fresh array still hits (content addressing) ...
    sthc(jnp.array(np.asarray(k)), x)
    assert cache.hits == 2
    # ... different kernel content misses
    sthc(k + 1.0, x)
    assert cache.misses == 2


def test_cache_key_separates_configs(rng):
    cache = GratingCache()
    x = _clips(rng)
    k = _kernels(rng)
    y_phys = STHC(STHCConfig(fidelity=fid.physical()), cache=cache)(k, x)
    y_ideal = STHC(STHCConfig(fidelity=fid.ideal()), cache=cache)(k, x)
    assert cache.misses == 2 and cache.hits == 0
    assert float(jnp.max(jnp.abs(y_phys - y_ideal))) > 0


def test_cache_ignores_query_only_knobs(rng):
    """Query-side config (chunking, kernel routing) doesn't change what
    was recorded — physically identical gratings must share one entry."""
    cache = GratingCache()
    x = _clips(rng)
    k = _kernels(rng)
    STHC(STHCConfig(fidelity=fid.physical()), cache=cache)(k, x)
    STHC(
        STHCConfig(fidelity=fid.physical(), use_pallas=True, osave_chunk_windows=4),
        cache=cache,
    )(k, x)
    assert cache.misses == 1 and cache.hits == 1


def test_ideal_grating_holds_single_tensor(rng):
    """Ideal mode has no ± stack; long-lived serving gratings must not
    retain redundant copies (stacked is None, plus aliases effective)."""
    k = _kernels(rng)
    g = QueryEngine(STHCConfig(fidelity=fid.ideal())).record(k, (20, 24, 10))
    assert g.stacked is None and g.minus is None
    assert g.plus is g.effective


def test_cache_bypassed_under_tracing(rng):
    cache = GratingCache()
    x = _clips(rng)
    k = _kernels(rng)
    sthc = STHC(STHCConfig(fidelity=fid.physical()), cache=cache)

    @jax.jit
    def run(k, x):
        return sthc(k, x)

    y = run(k, x)
    assert cache.misses == 0 and cache.hits == 0 and len(cache) == 0
    ref = STHC(STHCConfig(fidelity=fid.physical()))(k, x)
    np.testing.assert_allclose(y, ref, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(ref))))


def test_cache_lru_eviction(rng):
    cache = GratingCache(max_entries=2)
    x = _clips(rng)
    sthc = STHC(STHCConfig(fidelity=fid.ideal()), cache=cache)
    ks = [_kernels(np.random.RandomState(i)) for i in range(3)]
    for k in ks:
        sthc(k, x)
    assert len(cache) == 2 and cache.misses == 3
    sthc(ks[0], x)  # evicted → miss again
    assert cache.misses == 4


def test_cache_inflight_dedup_concurrent_misses(rng):
    """Concurrent misses for one key run engine.record exactly once —
    the losers wait on the in-flight recorder instead of thundering."""
    import threading
    import time as _time

    cache = GratingCache(max_entries=4)
    eng = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    k = _kernels(rng)
    calls = []
    orig = eng.record

    def slow_record(kernels, signal_shape):
        calls.append(1)
        _time.sleep(0.05)  # widen the race window
        return orig(kernels, signal_shape)

    eng.record = slow_record
    results = []

    def worker():
        results.append(cache.get_or_record(eng, k, (20, 24, 10)))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 1
    assert cache.misses == 1 and cache.hits == 3
    assert all(r is results[0] for r in results)


# -- stmul v2 ≡ v1 ≡ oracle -----------------------------------------------------


@pytest.mark.parametrize("C", [1, 3, 8, 9])  # spans the VPU/MXU routing split
def test_stmul_v2_matches_v1_and_oracle(C):
    rng = np.random.RandomState(C)
    sh = (6, 7, 5)
    xh = jnp.asarray(
        (rng.randn(2, C, *sh) + 1j * rng.randn(2, C, *sh)).astype(np.complex64)
    )
    g = jnp.asarray(
        (rng.randn(4, C, *sh) + 1j * rng.randn(4, C, *sh)).astype(np.complex64)
    )
    ref = stmul_ref.spectral_mac_ref(xh, g)
    tol = 1e-4 * float(jnp.max(jnp.abs(ref))) + 1e-6
    v1 = stmul_ops.spectral_mac(xh, g, version=1)
    v2 = stmul_ops.spectral_mac(xh, g, version=2)
    np.testing.assert_allclose(v1, ref, atol=tol)
    np.testing.assert_allclose(v2, ref, atol=tol)
    np.testing.assert_allclose(v2, v1, atol=tol)


def test_stmul_v2_tile_boundary():
    """F at / off the 512-lane tile boundary through the v2 kernel."""
    rng = np.random.RandomState(0)
    for F in (511, 512, 513):
        xh = jnp.asarray(
            (rng.randn(2, 1, F) + 1j * rng.randn(2, 1, F)).astype(np.complex64)
        )
        g = jnp.asarray(
            (rng.randn(3, 1, F) + 1j * rng.randn(3, 1, F)).astype(np.complex64)
        )
        got = stmul_ops.spectral_mac(xh, g, version=2)
        ref = stmul_ref.spectral_mac_ref(xh, g)
        np.testing.assert_allclose(got, ref, atol=1e-4)


def test_stmul_unknown_version_raises():
    xh = jnp.zeros((1, 1, 4, 4, 3), jnp.complex64)
    g = jnp.zeros((1, 1, 4, 4, 3), jnp.complex64)
    with pytest.raises(ValueError):
        stmul_ops.spectral_mac(xh, g, version=3)


# -- streaming (engine-owned overlap-save) ------------------------------------


@pytest.mark.parametrize("T", [9, 23, 37])  # ragged vs window/chunk grids
@pytest.mark.parametrize("chunk", [1, 2, 3, 8])
def test_batched_overlap_save_equals_one_shot(T, chunk, rng):
    x = jnp.asarray(rng.rand(1, 1, 10, 12, T).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 1, 3, 4, 3).astype(np.float32))
    ref = sc.direct_correlate3d(x, k, mode="valid")
    sthc = STHC(STHCConfig(fidelity=fid.ideal(), osave_chunk_windows=chunk))
    got = sthc.correlate_stream(k, x, block_t=7)
    np.testing.assert_allclose(
        got, ref, atol=2e-4 * float(jnp.max(jnp.abs(ref))) + 1e-5
    )


def test_correlate_stream_uses_cache_and_chunks(rng):
    cache = GratingCache()
    x = jnp.asarray(rng.rand(1, 1, 10, 12, 29).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 1, 3, 4, 3).astype(np.float32))
    sthc = STHC(STHCConfig(fidelity=fid.ideal(), osave_chunk_windows=3), cache=cache)
    ref = sc.direct_correlate3d(x, k, mode="valid")
    got = sthc.correlate_stream(k, x, block_t=8)
    np.testing.assert_allclose(
        got, ref, atol=2e-4 * float(jnp.max(jnp.abs(ref))) + 1e-5
    )
    sthc.correlate_stream(k, x, block_t=8)
    assert cache.hits == 1 and cache.misses == 1


@pytest.mark.parametrize("T", [33, 40])  # ragged vs window/chunk grids
@pytest.mark.parametrize("chunk", [1, 4])
def test_streaming_physical_equals_one_shot_paper_geometry(T, chunk, rng):
    """The pinned acceptance property: streaming physical correlation ==
    one-shot physical correlation at the paper geometry (30×40×8 kernels
    on 60×80 frames).  Record-time physics live on the reference's own
    temporal grid and query encoding uses a stream-global SLM scale, so
    the coherence-window decomposition is exactly lossless — the
    deployment of Fig. 1C serves the *same* physical model the accuracy
    experiments validate."""
    x = jnp.asarray(rng.rand(1, 1, 60, 80, T).astype(np.float32))
    k = jnp.asarray(rng.randn(9, 1, 30, 40, 8).astype(np.float32))
    ref = STHC(STHCConfig(fidelity=fid.physical()))(k, x)
    sthc = STHC(STHCConfig(fidelity=fid.physical(), osave_chunk_windows=chunk))
    got = sthc.correlate_stream(k, x, block_t=16)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel <= 1e-4, rel


def test_streaming_physical_small_geometry_ragged(rng):
    """Same property off the paper grid: ragged T vs block, odd shapes."""
    x = jnp.asarray(rng.rand(2, 1, 20, 24, 29).astype(np.float32))
    k = jnp.asarray(rng.randn(3, 1, 7, 9, 4).astype(np.float32))
    ref = STHC(STHCConfig(fidelity=fid.physical()))(k, x)
    got = STHC(
        STHCConfig(fidelity=fid.physical(), osave_chunk_windows=3)
    ).correlate_stream(k, x, block_t=11)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel <= 1e-4, rel


def test_query_stream_rejects_mismatched_frame_size(rng):
    k = jnp.asarray(rng.randn(2, 1, 3, 4, 3).astype(np.float32))
    sthc = STHC(STHCConfig(fidelity=fid.ideal()))
    grating = sthc.record(k, (12, 12, 8))
    with pytest.raises(ValueError, match="spatial dims"):
        sthc.engine.query_stream(grating, jnp.zeros((1, 1, 16, 16, 20)))


def test_video_server_rejects_mismatched_frame_size(rng):
    from repro.launch.serve import VideoSearchConfig, VideoSearchServer

    k = jnp.asarray(rng.randn(2, 1, 3, 4, 3).astype(np.float32))
    server = VideoSearchServer(k, (12, 12), VideoSearchConfig(window_frames=8))
    # the server pre-validates geometry upfront (before any device work)
    with pytest.raises(ValueError, match="server frame size"):
        server.search(jnp.zeros((1, 1, 16, 16, 20), jnp.float32))


def test_video_server_serves_physical_mode(rng):
    """The old NotImplementedError path is gone: physical-mode serving
    scores equal the one-shot physical correlator's peak responses."""
    from repro.launch.serve import VideoSearchConfig, VideoSearchServer

    k = jnp.asarray(rng.randn(2, 1, 3, 4, 3).astype(np.float32))
    clip = jnp.asarray(rng.rand(1, 1, 12, 12, 20).astype(np.float32))
    server = VideoSearchServer(
        k, (12, 12), VideoSearchConfig(window_frames=8, fidelity=fid.physical())
    )
    out = server.search(clip)
    ref = STHC(STHCConfig(fidelity=fid.physical()))(k, clip)
    want = np.asarray(jnp.max(ref.reshape(1, 2, -1), axis=-1))
    np.testing.assert_allclose(out["scores"], want, rtol=1e-4)


# -- stmul MXU-routing knob ---------------------------------------------------


@pytest.mark.parametrize("min_mxu_c", [1, 99])  # force MXU / force VPU
@pytest.mark.parametrize("C", [3, 8])
def test_stmul_min_mxu_c_routing_matches_oracle(min_mxu_c, C):
    """Both contraction routes agree with the oracle at any threshold —
    the real-TPU tuning knob changes routing, never semantics."""
    rng = np.random.RandomState(C)
    sh = (6, 7, 5)
    xh = jnp.asarray(
        (rng.randn(2, C, *sh) + 1j * rng.randn(2, C, *sh)).astype(np.complex64)
    )
    g = jnp.asarray(
        (rng.randn(4, C, *sh) + 1j * rng.randn(4, C, *sh)).astype(np.complex64)
    )
    ref = stmul_ref.spectral_mac_ref(xh, g)
    got = stmul_ops.spectral_mac(xh, g, version=2, min_mxu_c=min_mxu_c)
    np.testing.assert_allclose(
        got, ref, atol=1e-4 * float(jnp.max(jnp.abs(ref))) + 1e-6
    )


def test_stmul_min_mxu_c_routed_from_config(rng):
    """STHCConfig.stmul_min_mxu_c reaches the kernel: forcing the MXU
    route through the engine still matches the jnp path."""
    x = _clips(rng, C=3)
    k = _kernels(rng, C=3)
    ref = STHC(STHCConfig(fidelity=fid.physical()))(k, x)
    got = STHC(
        STHCConfig(fidelity=fid.physical(), use_pallas=True, stmul_min_mxu_c=1)
    )(k, x)
    rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
    assert rel <= 1e-4, rel


# -- engine as a pure function ----------------------------------------------------


def test_engine_record_query_jit_friendly(rng):
    """record + query compose under jit (grating as closed-over constant)."""
    x = _clips(rng)
    k = _kernels(rng)
    engine = QueryEngine(STHCConfig(fidelity=fid.physical()))
    grating = engine.record(k, x.shape[-3:])
    eager = engine.query(grating, x)
    jitted = jax.jit(lambda x: engine.query(grating, x))(x)
    np.testing.assert_allclose(
        eager, jitted, atol=1e-5 * float(jnp.max(jnp.abs(eager))) + 1e-6
    )


@pytest.mark.parametrize("store", ["float32", "bfloat16"])
@pytest.mark.parametrize("pipe", ["ideal", "physical"])
def test_query_is_one_program_equal_to_eager_composition(pipe, store, rng):
    """The jitted one-shot query equals the eager composition it
    replaced (encode, ``query_grating``, de-scale) to float32 rounding,
    and is traced once per clip shape: two calls at one shape and one
    at another add exactly two traces."""
    x = _clips(rng)
    engine = QueryEngine(
        STHCConfig(fidelity=getattr(fid, pipe)(), grating_dtype=store)
    )
    g = engine.record(_kernels(rng), x.shape[-3:])
    n0 = engine.query_traces
    got = engine.query(g, x)
    engine.query(g, x)
    engine.query(g, _clips(rng, B=3))
    assert engine.query_traces - n0 == 2
    if g.encode:
        enc, x_scale = engine._encode(x, g.slm_bits)
    else:
        enc, x_scale = x, 1.0
    want = sc.query_grating(enc, g.effective_c, g.fft_shape, g.out_shape)
    want = want * x_scale
    np.testing.assert_allclose(
        got, want, rtol=0, atol=2e-6 * float(jnp.max(jnp.abs(want)))
    )


# -- pooled cross-tenant executor ---------------------------------------------


def test_query_many_matches_query_loop(rng):
    """A pooled batch of one-window clips (each clip its own stream)
    equals the per-tenant one-shot query loop: mixed O, a duplicate
    grating (two requests, one tenant) and mixed batch sizes in one
    call."""
    x1, x2 = _clips(rng, B=2), _clips(rng, B=1)
    eng = QueryEngine(STHCConfig(fidelity=fid.physical()))
    g1 = eng.record(_kernels(rng, O=3), (20, 24, 10))
    g2 = eng.record(_kernels(rng, O=5), (20, 24, 10))
    outs = eng.query_stream_many([(g1, x1), (g2, x2), (g1, x2)])
    refs = [eng.query(g1, x1), eng.query(g2, x2), eng.query(g1, x2)]
    for out, ref in zip(outs, refs):
        assert out.shape == ref.shape
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_query_many_paper_geometry_mixed_fidelity_one_pool_group(rng):
    """Acceptance: at the paper geometry, two tenants at *different*
    fidelities that share encode semantics and FFT geometry occupy ONE
    pool group — a single pooled dispatch serves both, equal to the
    per-tenant loop."""
    x = _clips(rng, B=1, H=60, W=80, T=16)
    k1 = _kernels(rng, O=9, kh=30, kw=40, kt=8)
    k2 = _kernels(rng, O=9, kh=30, kw=40, kt=8)
    eng_phys = QueryEngine(STHCConfig(fidelity=fid.physical()))
    sub = fid.pipeline(
        fid.PseudoNegative(), fid.SLMQuantize(), fid.IHBEnvelope(),
        name="sub",
    )
    eng_sub = QueryEngine(STHCConfig(fidelity=sub))
    g1 = eng_phys.record(k1, (60, 80, 16))
    g2 = eng_sub.record(k2, (60, 80, 16))
    requests = [(g1, x), (g2, x)]
    # same encode semantics (SLM at 8 bits) + same FFT grid -> one group
    assert len(eng_phys._group_requests(requests)) == 1
    before = eng_phys.pool_stats()["dispatches"]
    outs = eng_phys.query_stream_many(requests)
    assert eng_phys.pool_stats()["dispatches"] == before + 1
    refs = [eng_phys.query(g1, x), eng_sub.query(g2, x)]
    for out, ref in zip(outs, refs):
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_pooled_dispatch_single_forward_fft(rng):
    """The pooled dataflow claim: one group dispatch = exactly one
    forward FFT + one inverse FFT per window, however many tenants and
    stream rows it serves."""
    x = _clips(rng, B=1)
    eng = QueryEngine(STHCConfig(fidelity=fid.physical()))
    g1 = eng.record(_kernels(rng, O=3), (20, 24, 10))
    g2 = eng.record(_kernels(rng, O=3), (20, 24, 10))
    pool = eng._resident_arena([g1, g2]).pool
    rows = np.asarray(pool.o_start, np.int32)
    plan = eng.stream_plan_for(g1, x.shape[-1])
    assert plan.n_blocks == 1  # one window
    jaxpr = jax.make_jaxpr(
        lambda a, b: eng._stream_many_impl(
            (a, b), pool.re, pool.im, rows, ker_shape=g1.ker_shape,
            fft_shape=g1.fft_shape, plan=plan, encode=g1.encode,
            slm_bits=g1.slm_bits, n_out=pool.n_out, block_o=pool.block_o,
        )
    )(x, x + 1.0)
    assert _count_ffts(jaxpr.jaxpr, "RFFT") == 1
    assert _count_ffts(jaxpr.jaxpr, "IRFFT") == 1


@pytest.mark.parametrize("chunk", [1, 3])
def test_query_stream_many_matches_stream_loop(chunk, rng):
    """Pooled streaming equals per-tenant query_stream: ragged T vs the
    window grid, physical encoding, chunked windows."""
    cfg = STHCConfig(fidelity=fid.physical(), osave_chunk_windows=chunk)
    eng = QueryEngine(cfg)
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 11))
    g2 = eng.record(_kernels(rng, O=4), (20, 24, 11))
    x1 = jnp.asarray(rng.rand(1, 1, 20, 24, 29).astype(np.float32))
    x2 = jnp.asarray(rng.rand(2, 1, 20, 24, 29).astype(np.float32))
    outs = eng.query_stream_many([(g1, x1), (g2, x2)])
    refs = [eng.query_stream(g1, x1), eng.query_stream(g2, x2)]
    for out, ref in zip(outs, refs):
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_query_many_pallas_grouped_matches_dense(rng):
    """The grouped Pallas launch and the dense gather path agree."""
    x = _clips(rng, B=2)
    dense = QueryEngine(STHCConfig(fidelity=fid.physical()))
    pallas = QueryEngine(
        STHCConfig(fidelity=fid.physical(), use_pallas=True)
    )
    k1, k2 = _kernels(rng, O=3), _kernels(rng, O=5)
    gd1, gd2 = dense.record(k1, (20, 24, 10)), dense.record(k2, (20, 24, 10))
    gp1, gp2 = pallas.record(k1, (20, 24, 10)), pallas.record(k2, (20, 24, 10))
    outs_d = dense.query_stream_many([(gd1, x), (gd2, x)])
    outs_p = pallas.query_stream_many([(gp1, x), (gp2, x)])
    for d, p in zip(outs_d, outs_p):
        rel = float(jnp.linalg.norm(p - d) / jnp.linalg.norm(d))
        assert rel <= 1e-4, rel
    assert pallas.pool_stats()["native_layout_dispatches"] == 1
    assert dense.pool_stats()["native_layout_dispatches"] == 0


def test_pool_arena_reused_across_calls(rng):
    """The packed arena is a stable buffer: repeated dispatches with the
    same resident gratings pack it once."""
    x = _clips(rng)
    eng = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 10))
    g2 = eng.record(_kernels(rng, O=2), (20, 24, 10))
    eng.set_resident([g1, g2])
    eng.query_stream_many([(g1, x), (g2, x)])
    arena = eng._resident_arena([g1])
    eng.query_stream_many([(g1, x), (g2, x)])
    eng.query_stream_many([(g2, x)])
    assert eng.pool_stats()["arena_builds"] == 1
    assert eng._resident_arena([g1, g2]) is arena


def test_query_many_rejects_channel_mismatch(rng):
    eng = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    g = eng.record(_kernels(rng, C=1), (20, 24, 10))
    with pytest.raises(ValueError, match="channels"):
        eng.query_stream_many([(g, _clips(rng, C=3))])


# -- grouped stmul kernel vs the v1 loop oracle --------------------------------


@pytest.mark.parametrize("C", [1, 8])  # one and many contracted channels
def test_stmul_grouped_matches_loop_oracle(C):
    """One grouped launch over a pooled lane-plane arena equals the
    per-request loop oracle — shared offsets included (two rows, one
    tenant) — and an arena that is not lane planes is refused."""
    rng = np.random.RandomState(C)
    s = (6, 7, 8)  # rfft grid: bins (6, 7, 5)
    sh = (6, 7, 5)
    B, n_out, block_o = 4, 4, 4
    pool = (rng.randn(12, C, *sh) + 1j * rng.randn(12, C, *sh)).astype(
        np.complex64
    )
    xh = jnp.asarray(
        (rng.randn(B, C, *sh) + 1j * rng.randn(B, C, *sh)).astype(
            np.complex64
        )
    )
    o_start = np.array([0, 4, 8, 4], np.int32)  # row 3 shares tenant 1
    ref = stmul_ref.spectral_mac_grouped_ref(
        xh, jnp.asarray(pool), o_start, n_out
    )
    ref_r, ref_i = sc.to_lane_planes(jnp.real(ref), jnp.imag(ref), s)
    xr, xi = sc.to_lane_planes(jnp.real(xh), jnp.imag(xh), s)
    pr, pi = sc.to_lane_planes(
        jnp.asarray(pool.real), jnp.asarray(pool.imag), s
    )
    assert pr.shape == (12, C, 5 * 8, 128)
    got_r, got_i = stmul_ops._mac_grouped_planes(
        xr, xi, pr, pi, o_start, n_out, block_o=block_o, block_f=None
    )
    tol = 1e-4 * float(jnp.max(jnp.abs(ref))) + 1e-6
    np.testing.assert_allclose(got_r, ref_r, atol=tol)
    np.testing.assert_allclose(got_i, ref_i, atol=tol)
    # bf16 arena planes (half-precision grating storage): f32-accumulated
    got_r, got_i = stmul_ops._mac_grouped_planes(
        xr, xi, pr.astype(jnp.bfloat16), pi.astype(jnp.bfloat16),
        o_start, n_out, block_o=block_o, block_f=None,
    )
    err = jnp.sqrt(
        jnp.sum((got_r - ref_r) ** 2) + jnp.sum((got_i - ref_i) ** 2)
    )
    rel = float(err / jnp.linalg.norm(ref))
    assert rel <= 2e-2, rel
    with pytest.raises(ValueError, match="lane planes"):
        stmul_ops._mac_grouped_planes(
            jnp.real(xh), jnp.imag(xh), jnp.asarray(pool.real),
            jnp.asarray(pool.imag), o_start, n_out, block_o=block_o,
            block_f=None,
        )


# -- half-precision (bf16 split-real) grating storage --------------------------


def test_bf16_storage_halves_nbytes_and_cache_bytes(rng):
    """STHCConfig.grating_dtype='bfloat16' stores split-real planes at
    exactly half the serving grating's bytes, and the cache byte
    accounting sees the halved footprint."""
    k = _kernels(rng)
    for pipe in (fid.ideal(), fid.physical()):
        f32 = QueryEngine(
            STHCConfig(fidelity=pipe, keep_stacked=False)
        ).record(k, (20, 24, 10))
        bf16 = QueryEngine(
            STHCConfig(
                fidelity=pipe, keep_stacked=False, grating_dtype="bfloat16"
            )
        ).record(k, (20, 24, 10))
        assert bf16.storage_dtype == "bfloat16"
        assert bf16.effective is None and bf16.eff_re is not None
        assert bf16.nbytes * 2 == f32.nbytes
    cache = GratingCache()
    sthc = STHC(
        STHCConfig(
            fidelity=fid.physical(),
            keep_stacked=False,
            grating_dtype="bfloat16",
        ),
        cache=cache,
    )
    g = sthc.record(k, (20, 24, 10))
    assert cache.nbytes == g.nbytes


def test_bf16_pooled_query_close_to_f32(rng):
    """bf16-at-rest, f32-accumulation: one-shot and pooled queries stay
    within tolerance of the f32 grating, and the pooled bf16 answer (a
    one-window stream) equals the per-tenant bf16 query."""
    x = _clips(rng)
    k = _kernels(rng)
    f32 = QueryEngine(STHCConfig(fidelity=fid.physical()))
    bf16 = QueryEngine(
        STHCConfig(fidelity=fid.physical(), grating_dtype="bfloat16")
    )
    gf, gb = f32.record(k, (20, 24, 10)), bf16.record(k, (20, 24, 10))
    yf, yb = f32.query(gf, x), bf16.query(gb, x)
    rel = float(jnp.linalg.norm(yb - yf) / jnp.linalg.norm(yf))
    assert rel <= 2e-2, rel
    (pooled,) = bf16.query_stream_many([(gb, x)])
    rel = float(jnp.linalg.norm(pooled - yb) / jnp.linalg.norm(yb))
    assert rel <= 1e-5, rel


def test_bf16_cache_key_never_aliases_f32(rng):
    """Same kernel bytes under the two storage dtypes are two cache
    entries — a lookup can never serve the other precision's grating."""
    cache = GratingCache()
    x = _clips(rng)
    k = _kernels(rng)
    STHC(STHCConfig(fidelity=fid.ideal()), cache=cache)(k, x)
    STHC(
        STHCConfig(fidelity=fid.ideal(), grating_dtype="bfloat16"),
        cache=cache,
    )(k, x)
    assert cache.misses == 2 and cache.hits == 0


def test_default_storage_layout_unchanged(rng):
    """grating_dtype defaults to f32: the recorded layout is the
    pre-knob complex64 tensor (bit-identical paths), and unknown
    dtypes are rejected loudly."""
    g = QueryEngine(STHCConfig(fidelity=fid.physical())).record(
        _kernels(rng), (20, 24, 10)
    )
    assert g.storage_dtype == "float32"
    assert g.effective is not None and g.eff_re is None
    assert g.effective.dtype == jnp.complex64
    with pytest.raises(ValueError, match="grating_dtype"):
        STHCConfig(fidelity=fid.ideal(), grating_dtype="float16")


# -- shared-stream clip-dedup + bounded-memory streaming ----------------------


def test_query_many_clip_dedup_paper_geometry_matches_loop(rng):
    """Acceptance: deduped shared-stream fan-out equals the per-request
    loop to float tolerance at the paper geometry — four tenants'
    kernel banks correlated against ONE clip in parallel (the paper's
    headline dataflow), answered from one physical batch row reading
    the union of their O-slices."""
    x = _clips(rng, B=1, H=60, W=80, T=16)
    eng = QueryEngine(STHCConfig(fidelity=fid.physical()))
    gs = [
        eng.record(_kernels(rng, O=3, kh=30, kw=40, kt=8), (60, 80, 16))
        for _ in range(4)
    ]
    before = eng.pool_stats()
    outs = eng.query_stream_many([(g, x) for g in gs])
    after = eng.pool_stats()
    for g, out in zip(gs, outs):
        ref = eng.query(g, x)
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel
    # 4 clip rows offered, 1 physical row dispatched
    assert after["rows_offered"] - before["rows_offered"] == 4
    assert after["rows_dispatched"] - before["rows_dispatched"] == 1
    assert after["rows_saved"] - before["rows_saved"] == 3


def test_query_many_dedup_is_content_addressed_not_identity(rng):
    """Two distinct array objects with equal bytes dedup; equal shapes
    with different bytes do not."""
    a = rng.rand(1, 1, 20, 24, 10).astype(np.float32)
    same = jnp.asarray(a.copy())
    also_same = jnp.asarray(a.copy())
    different = jnp.asarray(rng.rand(1, 1, 20, 24, 10).astype(np.float32))
    eng = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 10))
    g2 = eng.record(_kernels(rng, O=3), (20, 24, 10))
    before = eng.pool_stats()
    outs = eng.query_stream_many(
        [(g1, same), (g2, also_same), (g1, different)]
    )
    delta = {
        k: eng.pool_stats()[k] - before[k] for k in ("rows_offered", "rows_dispatched")
    }
    assert delta == {"rows_offered": 3, "rows_dispatched": 2}
    for out, (g, x) in zip(outs, [(g1, same), (g2, also_same), (g1, different)]):
        ref = eng.query(g, x)
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_query_many_dedup_off_is_row_per_request(rng):
    """dedup=False keeps the one-row-per-request baseline (the
    benchmark's undeduped pooled mode) with identical answers."""
    x = _clips(rng, B=1)
    eng = QueryEngine(STHCConfig(fidelity=fid.physical()))
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 10))
    g2 = eng.record(_kernels(rng, O=4), (20, 24, 10))
    before = eng.pool_stats()
    outs = eng.query_stream_many([(g1, x), (g2, x)], dedup=False)
    after = eng.pool_stats()
    assert after["rows_dispatched"] - before["rows_dispatched"] == 2
    assert after["rows_saved"] == before["rows_saved"]
    for out, g in zip(outs, (g1, g2)):
        ref = eng.query(g, x)
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_query_stream_many_clip_dedup_paper_geometry_matches_loop(rng):
    """Acceptance (streaming): N tenants fanning out over one shared
    stream — pooled + deduped overlap-save equals the per-request
    query_stream loop to float tolerance at the paper's frame/kernel
    geometry, and the whole fan-out dispatches ONE physical clip row."""
    cfg = STHCConfig(fidelity=fid.physical(), osave_chunk_windows=2)
    eng = QueryEngine(cfg)
    gs = [
        eng.record(_kernels(rng, O=3, kh=30, kw=40, kt=8), (60, 80, 16))
        for _ in range(3)
    ]
    x = jnp.asarray(rng.rand(1, 1, 60, 80, 40).astype(np.float32))
    before = eng.pool_stats()
    outs = eng.query_stream_many([(g, x) for g in gs])
    after = eng.pool_stats()
    assert after["rows_offered"] - before["rows_offered"] == 3
    assert after["rows_dispatched"] - before["rows_dispatched"] == 1
    for g, out in zip(gs, outs):
        ref = eng.query_stream(g, x)
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_query_stream_many_dedup_mixed_clips_and_batches(rng):
    """Dedup with a mixed composition: two tenants on one shared stream
    plus a third on its own — splits slice the right O-windows out of
    the shared row's union span."""
    eng = QueryEngine(STHCConfig(fidelity=fid.physical()))
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 11))
    g2 = eng.record(_kernels(rng, O=5), (20, 24, 11))
    shared = jnp.asarray(rng.rand(1, 1, 20, 24, 29).astype(np.float32))
    own = jnp.asarray(rng.rand(1, 1, 20, 24, 29).astype(np.float32))
    outs = eng.query_stream_many([(g1, shared), (g2, shared), (g2, own)])
    refs = [
        eng.query_stream(g1, shared),
        eng.query_stream(g2, shared),
        eng.query_stream(g2, own),
    ]
    for out, ref in zip(outs, refs):
        assert out.shape == ref.shape
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_query_stream_many_dedup_pallas_matches_dense(rng):
    """The grouped Pallas launch serves dedup union spans (aligned
    row offsets + dispatch-time arena padding) identically to the
    dense gather path."""
    k1, k2 = _kernels(rng, O=2, C=2), _kernels(rng, O=3, C=2)
    dense = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    pallas = QueryEngine(STHCConfig(fidelity=fid.ideal(), use_pallas=True))
    gd1, gd2 = dense.record(k1, (20, 24, 10)), dense.record(k2, (20, 24, 10))
    gp1, gp2 = pallas.record(k1, (20, 24, 10)), pallas.record(k2, (20, 24, 10))
    x = _clips(rng, B=1, C=2, T=26)
    outs_d = dense.query_stream_many([(gd1, x), (gd2, x)])
    outs_p = pallas.query_stream_many([(gp1, x), (gp2, x)])
    for d, p in zip(outs_d, outs_p):
        rel = float(jnp.linalg.norm(p - d) / jnp.linalg.norm(d))
        assert rel <= 1e-4, rel


@pytest.mark.parametrize("fidelity", ["ideal", "physical"])
def test_query_stream_chunked_cursor_equals_one_shot(fidelity, rng):
    """Acceptance: bounded-memory chunked streaming equals the one-shot
    (unbounded) correlation to float tolerance, at constant peak
    buffer, for both an un-encoded and an SLM-encoded pipeline (the
    stream-global scale must survive chunking)."""
    pipe = fid.ideal() if fidelity == "ideal" else fid.physical()
    eng = QueryEngine(STHCConfig(fidelity=pipe, osave_chunk_windows=2))
    g = eng.record(_kernels(rng, O=2, kh=7, kw=9, kt=4), (20, 24, 12))
    x = jnp.asarray(rng.rand(2, 1, 20, 24, 77).astype(np.float32))
    one_shot = eng.query_stream(g, x)
    chunked = eng.query_stream(g, x, max_buffer_windows=3)
    np.testing.assert_allclose(
        np.asarray(chunked),
        np.asarray(one_shot),
        atol=1e-6 * float(jnp.max(jnp.abs(one_shot))),
    )
    # the cursor really ran multiple bounded segments
    plan = eng.stream_plan_for(g, x.shape[-1])
    cursor = sc.StreamCursor(plan, 3)
    assert len(cursor) > 1
    assert cursor.peak_buffer_frames == 2 * plan.step + plan.block_t


def test_query_stream_chunked_paper_geometry_long_clip(rng):
    """Acceptance at paper geometry: a stream far longer than the
    device buffer (max_buffer_windows windows) serves exactly equal to
    one-shot streaming; every segment buffer stays at the constant
    bound regardless of T."""
    cfg = STHCConfig(fidelity=fid.physical())
    eng = QueryEngine(cfg)
    g = eng.record(_kernels(rng, O=2, kh=30, kw=40, kt=8), (60, 80, 16))
    x = jnp.asarray(rng.rand(1, 1, 60, 80, 70).astype(np.float32))
    one_shot = eng.query_stream(g, x)
    chunked = eng.query_stream(g, x, max_buffer_windows=2)
    np.testing.assert_allclose(
        np.asarray(chunked),
        np.asarray(one_shot),
        atol=1e-6 * float(jnp.max(jnp.abs(one_shot))),
    )
    plan = eng.stream_plan_for(g, x.shape[-1])
    cursor = sc.StreamCursor(plan, 2)
    bound = plan.step + plan.block_t
    assert all(seg.frames <= bound for seg in cursor)
    # the bound is independent of T: a 10x longer stream plans the same
    # per-segment buffer
    long_plan = eng.stream_plan_for(g, 10 * x.shape[-1])
    assert sc.StreamCursor(long_plan, 2).peak_buffer_frames <= bound


def test_query_stream_many_chunked_matches_unchunked(rng):
    """Pooled + deduped + chunked: the full stream-centric hot path
    equals the unbounded pooled pass and the per-request loop."""
    eng = QueryEngine(STHCConfig(fidelity=fid.physical()))
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 11))
    g2 = eng.record(_kernels(rng, O=3), (20, 24, 11))
    x = jnp.asarray(rng.rand(1, 1, 20, 24, 53).astype(np.float32))
    unbounded = eng.query_stream_many([(g1, x), (g2, x)])
    bounded = eng.query_stream_many(
        [(g1, x), (g2, x)], max_buffer_windows=2
    )
    for u, b, g in zip(unbounded, bounded, (g1, g2)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(u),
            atol=1e-6 * float(jnp.max(jnp.abs(u))),
        )
        ref = eng.query_stream(g, x)
        rel = float(jnp.linalg.norm(b - ref) / jnp.linalg.norm(ref))
        assert rel <= 1e-5, rel


def test_osave_max_buffer_windows_config_validation():
    with pytest.raises(ValueError, match="osave_max_buffer_windows"):
        STHCConfig(fidelity=fid.ideal(), osave_max_buffer_windows=0)
    cfg = STHCConfig(fidelity=fid.ideal(), osave_max_buffer_windows=4)
    assert cfg.osave_max_buffer_windows == 4
