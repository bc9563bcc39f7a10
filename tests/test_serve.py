"""Multi-tenant video-search serving: shared grating cache with
entry/byte-budget LRU eviction, per-tenant routing, batched scheduling of
concurrent streams, serving metrics, and hybrid long-clip inference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hybrid
from repro.core import fidelity as fid
from repro.core.engine import GratingCache, QueryEngine
from repro.core.sthc import STHC, STHCConfig
from repro.launch.serve import (
    HybridClassifierServer,
    VideoSearchConfig,
    VideoSearchServer,
)


def _kernels(seed, O=2, kt=3):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(O, 1, 3, 4, kt).astype(np.float32))


def _clip(seed, B=1, T=20, H=12, W=12):
    rng = np.random.RandomState(100 + seed)
    return jnp.asarray(rng.rand(B, 1, H, W, T).astype(np.float32))


def test_cfg_default_is_not_shared():
    """Regression for the shared mutable default: each server must own a
    fresh VideoSearchConfig instance."""
    a = VideoSearchServer(_kernels(0), (12, 12))
    b = VideoSearchServer(_kernels(1), (12, 12))
    assert a.cfg is not b.cfg
    a.cfg.window_frames = 7
    assert b.cfg.window_frames == VideoSearchConfig().window_frames


def test_multi_tenant_shared_cache_eviction_and_rerecord():
    """Record N+1 tenants into an N-entry cache: the LRU tenant is
    evicted (in registration order), and querying it re-records on a
    cache miss — the medium is transparently re-written."""
    cfg = VideoSearchConfig(window_frames=8, cache_entries=2)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    for i, name in enumerate(["a", "b", "c"]):
        server.add_tenant(name, _kernels(i))
    stats = server.cache.stats()
    assert stats["entries"] == 2 and stats["evictions"] == 1
    assert stats["misses"] == 3  # one record per tenant

    # 'a' was least-recently used -> evicted; searching it re-records
    out = server.search(_clip(0), tenant="a")
    assert out["tenant"] == "a"
    stats = server.cache.stats()
    assert stats["misses"] == 4 and stats["evictions"] == 2  # 'b' now out
    # 'c' stayed resident through all of this -> pure hit
    server.search(_clip(0), tenant="c")
    assert server.cache.stats()["hits"] >= 1


def test_cache_byte_budget_evicts():
    """The byte-sized budget evicts independently of the entry budget."""
    engine = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    probe = engine.record(_kernels(0), (12, 12, 8))
    # room for exactly one grating, many entries allowed
    cache = GratingCache(max_entries=64, max_bytes=int(probe.nbytes * 1.5))
    sthc = STHC(STHCConfig(fidelity=fid.ideal()), cache=cache)
    sthc.record(_kernels(1), (12, 12, 8))
    sthc.record(_kernels(2), (12, 12, 8))
    stats = cache.stats()
    assert stats["entries"] == 1 and stats["evictions"] == 1
    assert stats["bytes"] <= cache.max_bytes
    # re-recording the evicted set is a miss, not a hit
    sthc.record(_kernels(1), (12, 12, 8))
    assert cache.stats()["misses"] == 3


def test_oversized_grating_served_uncached_without_flushing_peers():
    """A grating larger than the whole byte budget must not evict the
    resident tenants while failing to fit — it is served uncached."""
    engine = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    small = engine.record(_kernels(0), (12, 12, 8))
    cache = GratingCache(max_entries=64, max_bytes=int(small.nbytes * 1.5))
    sthc = STHC(STHCConfig(fidelity=fid.ideal()), cache=cache)
    sthc.record(_kernels(1), (12, 12, 8))  # resident
    big = sthc.record(_kernels(2, O=8), (16, 16, 16))  # exceeds budget alone
    assert big.nbytes > cache.max_bytes
    stats = cache.stats()
    assert stats["entries"] == 1 and stats["evictions"] == 0
    # the small resident grating is still a hit
    sthc.record(_kernels(1), (12, 12, 8))
    assert cache.stats()["hits"] == 1


def test_remove_tenant_frees_cache_entry():
    """Removing a tenant invalidates its grating so it stops consuming
    the shared entry/byte budget (no phantom LRU pressure)."""
    cfg = VideoSearchConfig(window_frames=8, cache_entries=2)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0)).add_tenant("b", _kernels(1))
    server.remove_tenant("a")
    assert server.cache.stats()["entries"] == 1
    server.add_tenant("c", _kernels(2))  # fits beside 'b' — no eviction
    stats = server.cache.stats()
    assert stats["entries"] == 2 and stats["evictions"] == 0
    assert server.tenants == ["b", "c"]


def test_search_does_not_rehash_kernels(monkeypatch):
    """The tenant's kernel bytes are hashed once at registration; a
    search must not re-derive the cache key per request."""
    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    monkeypatch.setattr(
        GratingCache,
        "key_for",
        staticmethod(lambda *a, **k: pytest.fail("key re-derived at query time")),
    )
    out = server.search(_clip(0))
    assert out["scores"].shape == (1, 2)
    assert server.cache.stats()["hits"] >= 1


def test_add_tenant_replacement_discards_old_grating():
    """Re-registering a tenant name swaps its grating instead of leaking
    the old one into the shared entry/byte budget."""
    cfg = VideoSearchConfig(window_frames=8, cache_entries=4)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0))
    bytes_one = server.cache.stats()["bytes"]
    server.add_tenant("a", _kernels(1))
    stats = server.cache.stats()
    assert stats["entries"] == 1 and stats["bytes"] == bytes_one
    assert server.tenants == ["a"]


def test_remove_tenant_keeps_entry_shared_with_identical_kernels():
    """Content-addressed keys: two tenants with byte-identical kernels
    share one cache entry; removing one must not cold-start the other."""
    cfg = VideoSearchConfig(window_frames=8, cache_entries=4)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    k = _kernels(0)
    server.add_tenant("a", k).add_tenant("b", jnp.array(np.asarray(k)))
    assert server.cache.stats()["entries"] == 1  # shared entry
    server.remove_tenant("a")
    assert server.cache.stats()["entries"] == 1  # 'b' still holds it
    misses = server.cache.stats()["misses"]
    server.search(_clip(0), tenant="b")  # pure hit, no re-record
    assert server.cache.stats()["misses"] == misses
    server.remove_tenant("b")
    assert server.cache.stats()["entries"] == 0  # last reference freed


def test_physical_serving_grating_drops_stacked():
    """Serving configs strip the raw ± stack: a cached physical grating
    charges only its hot-path (effective) bytes against cache_bytes,
    and still scores identically to the full-fidelity correlator."""
    server = VideoSearchServer(
        _kernels(0), (12, 12),
        VideoSearchConfig(window_frames=8, fidelity=fid.physical()),
    )
    g = server._grating("default")
    assert g.encode and g.stacked is None
    assert g.nbytes == int(g.effective.nbytes)
    assert server.cache.stats()["bytes"] == g.nbytes


def test_search_batch_groups_and_matches_individual():
    """Concurrent streams stack on the batch axis per (tenant, shape)
    group; results equal one-at-a-time searches, in request order."""
    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0)).add_tenant("b", _kernels(1, O=3))
    reqs = [("a", _clip(1)), ("b", _clip(2)), ("a", _clip(3))]
    batched = server.search_batch(reqs)
    for (tenant, clip), out in zip(reqs, batched):
        solo = server.search(clip, tenant=tenant)
        assert out["tenant"] == tenant
        np.testing.assert_allclose(out["scores"], solo["scores"], rtol=1e-5)
        np.testing.assert_array_equal(out["peak_frame"], solo["peak_frame"])


def test_search_batch_unknown_tenant():
    server = VideoSearchServer(_kernels(0), (12, 12))
    with pytest.raises(KeyError, match="unknown tenant"):
        server.search(_clip(0), tenant="nope")


def test_server_metrics_counters():
    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    server.search(_clip(0, B=2, T=20))
    m = server.metrics()
    assert m["queries"] == 1
    assert m["frames_total"] == 2 * 20  # both concurrent streams count
    assert m["windows_total"] >= 2
    cache = m["cache"]
    for key in ("hits", "misses", "evictions", "entries", "bytes"):
        assert key in cache
    assert cache["bytes"] > 0


def test_server_metrics_survive_tenant_churn():
    """Server-wide traffic totals must not rewind when a tenant is
    removed or its name re-registered with new kernels."""
    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    server.search(_clip(0, B=2, T=20))
    before = server.metrics()
    server.remove_tenant("default")
    server.add_tenant("default", _kernels(1))
    server.search(_clip(1, T=20))
    m = server.metrics()
    assert m["queries"] == before["queries"] + 1
    assert m["frames_total"] == before["frames_total"] + 20
    assert m["windows_total"] > before["windows_total"]


def test_spatially_oversized_kernels_rejected():
    server = VideoSearchServer(frame_hw=(12, 12))
    big = jnp.zeros((2, 1, 30, 40, 3), jnp.float32)
    with pytest.raises(ValueError, match="spatial size"):
        server.add_tenant("big", big)


def test_invalid_mode_rejected():
    with pytest.raises(ValueError, match="mode"):
        VideoSearchServer(
            _kernels(0), (12, 12), VideoSearchConfig(mode="Ideal")
        )


def test_hybrid_classify_stream_matches_per_segment():
    """Long-clip hybrid inference: each training-length segment of the
    streamed conv output classifies identically to a one-shot classify
    of that sub-clip (ideal mode; physical differs only in SLM scale)."""
    cfg = hybrid.HybridConfig(
        height=16, width=18, frames=8, num_kernels=2,
        k_h=5, k_w=6, k_t=3, pool_window=(4, 4, 2), hidden=8,
    )
    rng = np.random.RandomState(0)
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    server = HybridClassifierServer(params, cfg, physical=False)
    ot = cfg.conv_out_shape[2]
    n_seg = 3
    T = cfg.frames + (n_seg - 1) * ot
    clips = jnp.asarray(rng.rand(2, 1, 16, 18, T).astype(np.float32))
    preds = server.classify_stream(clips)
    assert preds.shape == (2, n_seg)
    for s in range(n_seg):
        sub = clips[..., s * ot : s * ot + cfg.frames]
        np.testing.assert_array_equal(preds[:, s], server.classify(sub))


def test_hybrid_conv_layer_stream_matches_digital():
    cfg = hybrid.HybridConfig(
        height=16, width=18, frames=8, num_kernels=2,
        k_h=5, k_w=6, k_t=3, pool_window=(4, 4, 2), hidden=8,
    )
    rng = np.random.RandomState(1)
    params = hybrid.init_params(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.rand(1, 1, 16, 18, 25).astype(np.float32))
    ref = hybrid.conv_layer_stream(params, x, cfg, impl="digital")
    got = hybrid.conv_layer_stream(params, x, cfg, impl="spectral")
    np.testing.assert_allclose(
        got, ref, atol=2e-4 * float(jnp.max(jnp.abs(ref))) + 1e-5
    )


# -- pooled cross-tenant serving ----------------------------------------------


def test_search_batch_pooled_matches_sequential_mixed_fidelity():
    """The pooled executor and the per-tenant-sequential baseline agree
    on a mixed-tenant, mixed-fidelity batch, and the dispatch counters
    attribute each mode."""
    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0)).add_tenant("b", _kernels(1, O=3))
    server.add_tenant("c", _kernels(2), fidelity=fid.physical())
    reqs = [
        ("a", _clip(1)), ("b", _clip(2)), ("c", _clip(3)), ("a", _clip(4)),
    ]
    pooled = server.search_batch(reqs, pooled=True)
    seq = server.search_batch(reqs, pooled=False)
    for p, s in zip(pooled, seq):
        assert p["tenant"] == s["tenant"]
        np.testing.assert_allclose(p["scores"], s["scores"], rtol=1e-4)
        np.testing.assert_array_equal(p["peak_frame"], s["peak_frame"])
    m = server.metrics()
    # one pooled dispatch for the whole batch vs one per tenant-group
    assert m["pooled_dispatches"] == 1
    assert m["sequential_dispatches"] == 3
    # traffic counted once per request set regardless of mode
    assert m["queries"] == 2 * len(reqs)


def test_search_batch_pooled_default_from_config():
    server = VideoSearchServer(
        _kernels(0), (12, 12),
        VideoSearchConfig(window_frames=8, pooled_queries=True),
    )
    server.search(_clip(0))
    assert server.metrics()["pooled_dispatches"] == 1
    server2 = VideoSearchServer(
        _kernels(0), (12, 12),
        VideoSearchConfig(window_frames=8, pooled_queries=False),
    )
    server2.search(_clip(0))
    assert server2.metrics()["sequential_dispatches"] == 1


def test_serving_bf16_grating_storage():
    """VideoSearchConfig.grating_dtype='bfloat16': half the cache bytes
    of the f32 server for the same tenants, scores within tolerance."""
    kw = dict(window_frames=8, chunk_windows=2)
    f32 = VideoSearchServer(
        frame_hw=(12, 12), cfg=VideoSearchConfig(**kw)
    )
    bf16 = VideoSearchServer(
        frame_hw=(12, 12),
        cfg=VideoSearchConfig(grating_dtype="bfloat16", **kw),
    )
    for srv in (f32, bf16):
        srv.add_tenant("a", _kernels(0), fidelity=fid.physical())
        srv.add_tenant("b", _kernels(1))
    assert bf16.cache.nbytes * 2 == f32.cache.nbytes
    out_f = f32.search(_clip(0), tenant="a")
    out_b = bf16.search(_clip(0), tenant="a")
    scale = float(np.max(np.abs(out_f["scores"]))) or 1.0
    assert float(np.max(np.abs(out_f["scores"] - out_b["scores"]))) <= (
        2e-2 * scale
    )


# -- async microbatch scheduler -----------------------------------------------


def test_scheduler_batches_and_matches_search_batch():
    """Submitted futures resolve to the same detections search_batch
    returns, requests coalesce into microbatches, and metrics report
    latency percentiles."""
    from repro.launch.serve import MicrobatchScheduler

    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0)).add_tenant("b", _kernels(1))
    reqs = [("a", _clip(1)), ("b", _clip(2)), ("a", _clip(3))]
    want = server.search_batch(reqs)
    with MicrobatchScheduler(
        server, max_queue=8, max_batch=4, batch_wait_s=0.05
    ) as sched:
        futs = [sched.submit(t, c) for t, c in reqs]
        outs = [f.result(timeout=60) for f in futs]
        m = sched.metrics()
    for out, ref in zip(outs, want):
        assert out["tenant"] == ref["tenant"]
        np.testing.assert_allclose(out["scores"], ref["scores"], rtol=1e-4)
        assert out["queue_latency_s"] > 0
    assert m["submitted"] == 3 and m["completed"] == 3
    assert m["batches"] >= 1 and m["mean_batch_size"] > 1  # coalesced
    assert m["latency_p50_ms"] > 0
    assert m["latency_p99_ms"] >= m["latency_p50_ms"]


def test_scheduler_sheds_on_full_queue():
    """Admission control: a full bounded queue sheds instead of piling
    up — RequestRejected + the rejected counter."""
    import time as _time

    from repro.launch.serve import MicrobatchScheduler, RequestRejected

    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    orig = server.search_batch

    def slow_search_batch(reqs, pooled=None, **kw):
        _time.sleep(0.25)  # hold the batcher busy so the queue fills
        return orig(reqs, pooled=pooled, **kw)

    server.search_batch = slow_search_batch
    with MicrobatchScheduler(
        server, max_queue=1, max_batch=1, batch_wait_s=0.0
    ) as sched:
        futs, shed = [], 0
        for i in range(8):
            try:
                futs.append(sched.submit("default", _clip(i)))
            except RequestRejected:
                shed += 1
        assert shed > 0
        assert sched.metrics()["rejected"] == shed
        for f in futs:
            f.result(timeout=60)  # admitted requests still complete
    assert sched.metrics()["completed"] == len(futs)


def test_scheduler_bad_request_fails_only_its_future():
    """One invalid request must not poison its microbatch: the good
    requests complete, the bad future carries the error."""
    from repro.launch.serve import MicrobatchScheduler

    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    with MicrobatchScheduler(
        server, max_queue=8, max_batch=4, batch_wait_s=0.05
    ) as sched:
        good = sched.submit("default", _clip(0))
        bad = sched.submit("nope", _clip(1))
        good2 = sched.submit("default", _clip(2))
        with pytest.raises(KeyError, match="unknown tenant"):
            bad.result(timeout=60)
        assert good.result(timeout=60)["scores"].shape == (1, 2)
        assert good2.result(timeout=60)["scores"].shape == (1, 2)
    assert sched.metrics()["failed"] == 1


def test_scheduler_close_fails_pending_futures():
    import time as _time

    from repro.launch.serve import MicrobatchScheduler

    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    orig = server.search_batch

    def slow_search_batch(reqs, pooled=None, **kw):
        _time.sleep(0.3)
        return orig(reqs, pooled=pooled, **kw)

    server.search_batch = slow_search_batch
    sched = MicrobatchScheduler(
        server, max_queue=8, max_batch=1, batch_wait_s=0.0
    )
    futs = [sched.submit("default", _clip(i)) for i in range(4)]
    sched.close()
    with pytest.raises(RuntimeError):
        sched.submit("default", _clip(9))
    states = [("done" if f.done() else "pending") for f in futs]
    assert all(s == "done" for s in states)  # resolved or failed, not hung


# -- grating cache under concurrent tenant churn ------------------------------


def test_grating_cache_threaded_churn_byte_accounting():
    """Threaded add/evict/discard churn against one shared cache: the
    byte ledger must equal the sum of resident gratings afterwards —
    including half-priced bf16 entries — and budgets must hold."""
    import threading

    from repro.core import fidelity as fid_mod

    engines = [
        QueryEngine(
            STHCConfig(fidelity=fid_mod.ideal(), keep_stacked=False)
        ),
        QueryEngine(
            STHCConfig(
                fidelity=fid_mod.ideal(),
                keep_stacked=False,
                grating_dtype="bfloat16",
            )
        ),
    ]
    kernels = [_kernels(i) for i in range(6)]
    probe = engines[0].record(kernels[0], (12, 12, 8))
    cache = GratingCache(max_entries=4, max_bytes=int(probe.nbytes * 3.5))
    errors = []

    def worker(wid):
        rng = np.random.RandomState(wid)
        try:
            for step in range(30):
                eng = engines[step % 2]
                k = kernels[rng.randint(len(kernels))]
                key = GratingCache.key_for(k, (12, 12, 8), eng.config)
                if rng.rand() < 0.2:
                    cache.discard(key)
                else:
                    g = cache.get_or_record(eng, k, (12, 12, 8), key=key)
                    assert g.n_out == k.shape[0]
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = cache.stats()
    assert stats["entries"] <= 4
    assert stats["bytes"] <= cache.max_bytes
    assert stats["misses"] > 0
    # the ledger equals the residents exactly (white-box invariant)
    with cache._lock:
        assert cache._nbytes == sum(
            g.nbytes for g in cache._entries.values()
        )
        # bf16 residents charge exactly half their f32 twin's bytes
        for g in cache._entries.values():
            expected = probe.nbytes * g.n_out // probe.n_out
            if g.storage_dtype == "bfloat16":
                assert g.nbytes * 2 == expected
            else:
                assert g.nbytes == expected
    assert not cache._inflight  # no leaked in-flight markers


def test_video_server_threaded_tenant_churn():
    """Concurrent add/remove/search churn on one server: no exceptions
    besides expected unknown-tenant races, counters only grow, and
    removing every tenant drains the cache to zero bytes."""
    import threading

    cfg = VideoSearchConfig(window_frames=8, cache_entries=3)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    names = [f"t{i}" for i in range(4)]
    errors = []

    def worker(wid):
        rng = np.random.RandomState(wid)
        try:
            for step in range(12):
                name = names[rng.randint(len(names))]
                r = rng.rand()
                if r < 0.4:
                    server.add_tenant(name, _kernels(rng.randint(6)))
                elif r < 0.6:
                    try:
                        server.remove_tenant(name)
                    except KeyError:
                        pass  # raced another remover
                else:
                    try:
                        server.search(_clip(step), tenant=name)
                    except KeyError:
                        pass  # tenant removed mid-flight
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = server.cache.stats()
    assert stats["entries"] <= cfg.cache_entries
    for name in list(server.tenants):
        server.remove_tenant(name)
    stats = server.cache.stats()
    assert stats["entries"] == 0 and stats["bytes"] == 0


def test_scheduler_mixed_shapes_all_complete_and_coalesce():
    """Interleaved clip shapes: deferred (stashed) requests must still
    dispatch — and same-shape stash leftovers coalesce into one batch
    instead of draining as singletons."""
    from repro.launch.serve import MicrobatchScheduler

    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2)
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0)).add_tenant("b", _kernels(1))
    with MicrobatchScheduler(
        server, max_queue=32, max_batch=4, batch_wait_s=0.1
    ) as sched:
        futs = []
        for i in range(4):  # alternate two stream lengths (shapes)
            futs.append(sched.submit("a", _clip(i, T=20)))
            futs.append(sched.submit("b", _clip(i, T=24)))
        outs = [f.result(timeout=60) for f in futs]
        m = sched.metrics()
    assert all(o["scores"].shape == (1, 2) for o in outs)
    assert m["completed"] == 8
    # 8 requests of 2 shapes in <=4-deep batches: coalescing keeps the
    # dispatch count well under one-per-request
    assert m["batches"] <= 6


# -- per-tenant device models -------------------------------------------------


def test_per_tenant_device_models_route_and_cache_separately():
    """add_tenant(..., slm=..., atoms=...): same kernel bytes under two
    device models occupy two engines and two cache entries (no
    cross-device hits), and each tenant's answers match a single-tenant
    server built wholly at that device model."""
    from repro.core import atomic, optics

    k = _kernels(0)
    clip = _clip(0, T=24)
    cfg = VideoSearchConfig(window_frames=8, fidelity=fid.physical())
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("stock", k)
    server.add_tenant("coarse", k, slm=optics.SLMConfig(bits=4))
    server.add_tenant(
        "slow-atoms", k, atoms=atomic.AtomicConfig(t2_s=2e-3)
    )
    # three engines (three device fingerprints), three cache entries for
    # one set of kernel bytes
    assert len(server._sthcs) == 3
    assert server.cache.stats()["entries"] == 3

    outs = server.search_batch(
        [("stock", clip), ("coarse", clip), ("slow-atoms", clip)]
    )
    # oracle: one server per device model, default-configured otherwise
    for name, slm, atoms in (
        ("stock", None, None),
        ("coarse", optics.SLMConfig(bits=4), None),
        ("slow-atoms", None, atomic.AtomicConfig(t2_s=2e-3)),
    ):
        solo = VideoSearchServer(
            frame_hw=(12, 12),
            cfg=VideoSearchConfig(
                window_frames=8, fidelity=fid.physical(), slm=slm, atoms=atoms
            ),
        )
        solo.add_tenant("only", k)
        ref = solo.search(clip, tenant="only")
        got = next(o for o in outs if o["tenant"] == name)
        np.testing.assert_allclose(got["scores"], ref["scores"], rtol=1e-5)

    m = server.metrics()
    assert m["tenants"]["stock"]["device"] == "default"
    assert "bits=4" in m["tenants"]["coarse"]["device"]
    assert "t2=0.002" in m["tenants"]["slow-atoms"]["device"]


def test_device_tenants_pool_when_encode_semantics_match():
    """Record-time device physics (atoms) is baked into the grating, so
    a custom-atoms tenant still pools into the default tenants' single
    dispatch; a different SLM bit depth changes encode semantics and
    keeps its own group — both still answer correctly."""
    from repro.core import atomic, optics

    cfg = VideoSearchConfig(window_frames=8, fidelity=fid.physical())
    server = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    server.add_tenant("a", _kernels(0))
    server.add_tenant("b", _kernels(1), atoms=atomic.AtomicConfig(t2_s=2e-3))
    server.add_tenant("c", _kernels(2), slm=optics.SLMConfig(bits=4))
    clip = _clip(1, T=24)
    reqs = [("a", clip), ("b", clip), ("c", clip)]
    pooled = server.search_batch(reqs, pooled=True)
    seq = server.search_batch(reqs, pooled=False)
    for p, s in zip(pooled, seq):
        np.testing.assert_allclose(p["scores"], s["scores"], rtol=1e-5)
        np.testing.assert_array_equal(p["peak_frame"], s["peak_frame"])
    # a+b share one pool group (same 8-bit encode); c is its own: the
    # dedup collapsed a+b's shared clip onto one physical row
    d = server.metrics()["dedup"]
    assert d["rows_offered"] == 3 and d["rows_dispatched"] == 2


# -- shared-stream clip-dedup through the server ------------------------------


def test_search_batch_shared_clip_dedup_counters_and_equivalence():
    """The acceptance path end to end: N tenants searching ONE clip
    through search_batch — deduped pooled answers equal the sequential
    per-tenant loop, and metrics report the collapsed rows."""
    server = VideoSearchServer(
        frame_hw=(12, 12), cfg=VideoSearchConfig(window_frames=8)
    )
    for i in range(4):
        server.add_tenant(f"t{i}", _kernels(i))
    clip = _clip(2, T=32)
    reqs = [(f"t{i}", clip) for i in range(4)]
    pooled = server.search_batch(reqs, pooled=True)
    seq = server.search_batch(reqs, pooled=False)
    for p, s in zip(pooled, seq):
        np.testing.assert_allclose(p["scores"], s["scores"], rtol=1e-5)
        np.testing.assert_array_equal(p["peak_frame"], s["peak_frame"])
    d = server.metrics()["dedup"]
    assert d["rows_offered"] == 4
    assert d["rows_dispatched"] == 1
    assert d["rows_saved"] == 3
    # dedup off: the undeduped pooled baseline still matches
    undeduped = server.search_batch(reqs, pooled=True, dedup=False)
    for u, s in zip(undeduped, seq):
        np.testing.assert_allclose(u["scores"], s["scores"], rtol=1e-5)
    d2 = server.metrics()["dedup"]
    assert d2["rows_dispatched"] - d["rows_dispatched"] == 4  # no collapse


def test_search_batch_long_stream_chunked_matches_unbounded():
    """max_buffer_windows: a stream needing many more windows than the
    device buffer answers identically to the unbounded server."""
    k = _kernels(0)
    clip = _clip(3, T=96)
    bounded = VideoSearchServer(
        frame_hw=(12, 12),
        cfg=VideoSearchConfig(window_frames=8, max_buffer_windows=2),
    )
    unbounded = VideoSearchServer(
        frame_hw=(12, 12), cfg=VideoSearchConfig(window_frames=8)
    )
    for srv in (bounded, unbounded):
        srv.add_tenant("events", k)
    out_b = bounded.search(clip, tenant="events")
    out_u = unbounded.search(clip, tenant="events")
    np.testing.assert_allclose(out_b["scores"], out_u["scores"], rtol=1e-6)
    np.testing.assert_array_equal(out_b["peak_frame"], out_u["peak_frame"])
    assert out_b["windows"] == out_u["windows"]


# -- microbatch scheduler: dedup groups under close/cancel races ---------------


def test_scheduler_forms_dedup_groups_and_counts():
    """Same-clip requests across tenants land in one microbatch dedup
    group: the scheduler counter and the engine row counters agree."""
    from repro.launch.serve import MicrobatchScheduler

    server = VideoSearchServer(
        frame_hw=(12, 12), cfg=VideoSearchConfig(window_frames=8)
    )
    for i in range(3):
        server.add_tenant(f"t{i}", _kernels(i))
    clip = _clip(4, T=24)
    with MicrobatchScheduler(
        server, max_queue=8, max_batch=8, batch_wait_s=0.05
    ) as sched:
        futs = [sched.submit(f"t{i}", clip, block=True) for i in range(3)]
        outs = [f.result(timeout=120) for f in futs]
    for out, i in zip(outs, range(3)):
        assert out["tenant"] == f"t{i}"
    m = sched.metrics()
    assert m["completed"] == 3
    # at least two same-clip rows joined an existing dedup group (all
    # three when the batcher coalesced one batch)
    assert m["dedup_grouped"] >= 2
    assert server.metrics()["dedup"]["rows_saved"] >= 2


def test_scheduler_cancel_mid_dedup_group_does_not_poison_siblings():
    """Close/cancel race on the dedup-group path: requests sharing one
    clip where one future is cancelled before dispatch — the cancelled
    request must drop out of the batch while its same-clip siblings
    complete with correct results."""
    import threading
    import time as _time

    from repro.launch.serve import MicrobatchScheduler

    server = VideoSearchServer(
        frame_hw=(12, 12), cfg=VideoSearchConfig(window_frames=8)
    )
    for i in range(3):
        server.add_tenant(f"t{i}", _kernels(i))
    clip = _clip(5, T=24)
    ref = {
        f"t{i}": server.search(clip, tenant=f"t{i}")["scores"]
        for i in range(3)
    }

    orig = server.search_batch
    release = threading.Event()

    def gated_search_batch(reqs, pooled=None, **kw):
        release.wait(timeout=30)  # hold the first batch until cancelled
        return orig(reqs, pooled=pooled, **kw)

    server.search_batch = gated_search_batch
    with MicrobatchScheduler(
        server, max_queue=8, max_batch=1, batch_wait_s=0.0
    ) as sched:
        # batch 1 (size 1) occupies the batcher behind the gate; the
        # three same-clip requests queue up as the next dedup group
        blocker = sched.submit("t0", _clip(6, T=24))
        _time.sleep(0.05)
        futs = [sched.submit(f"t{i}", clip) for i in range(3)]
        assert futs[1].cancel()  # cancel a dedup-group member pre-dispatch
        release.set()
        assert futs[0].result(timeout=120)["tenant"] == "t0"
        assert futs[2].result(timeout=120)["tenant"] == "t2"
        np.testing.assert_allclose(
            futs[0].result()["scores"], ref["t0"], rtol=1e-5
        )
        np.testing.assert_allclose(
            futs[2].result()["scores"], ref["t2"], rtol=1e-5
        )
        blocker.result(timeout=120)
        with pytest.raises(Exception):  # cancelled future never resolves
            futs[1].result(timeout=5)
        # the scheduler survives: a fresh same-clip request still serves
        again = sched.submit("t1", clip, block=True)
        np.testing.assert_allclose(
            again.result(timeout=120)["scores"], ref["t1"], rtol=1e-5
        )
    m = sched.metrics()
    assert m["completed"] >= 4


def test_scheduler_close_fails_queued_dedup_group():
    """close() with a whole dedup group still queued: every member's
    future resolves (failed, not hung), including the shared-clip
    siblings."""
    import threading
    import time as _time

    from repro.launch.serve import MicrobatchScheduler

    server = VideoSearchServer(
        _kernels(0), (12, 12), VideoSearchConfig(window_frames=8)
    )
    orig = server.search_batch
    release = threading.Event()

    def gated_search_batch(reqs, pooled=None, **kw):
        release.wait(timeout=30)
        return orig(reqs, pooled=pooled, **kw)

    server.search_batch = gated_search_batch
    sched = MicrobatchScheduler(
        server, max_queue=8, max_batch=1, batch_wait_s=0.0
    )
    clip = _clip(7, T=24)
    blocker = sched.submit("default", _clip(8, T=24))
    _time.sleep(0.05)
    futs = [sched.submit("default", clip) for _ in range(3)]
    closer = threading.Thread(target=sched.close)
    closer.start()
    _time.sleep(0.05)
    release.set()
    closer.join(timeout=30)
    assert not closer.is_alive()
    for f in futs + [blocker]:
        assert f.done()  # resolved or failed, never hung
