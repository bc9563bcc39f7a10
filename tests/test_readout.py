"""Fused detection readout: the tiled top-K kernel vs its sort oracle,
associative-merge properties (re-chunking / permutation invariance), the
engine's fused streaming paths vs the stitched-volume oracle (paper
geometry, chunked + dedup + bf16 rows), and the serving entry points'
bitwise-identical scores across search / search_batch / pooled /
sequential / fused / stitched — plus the NaN-quarantine interaction with
``guard_scores`` on the fused path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import given, settings, st  # hypothesis, or deterministic fallback

from repro.core import fidelity as fid
from repro.core.engine import QueryEngine, TopKDetections, TOPK_EMPTY_IDX
from repro.core.sthc import STHCConfig
from repro.kernels.stmul import kernel as stmul_kernel
from repro.kernels.stmul import ops as stmul_ops
from repro.kernels.stmul import ref as stmul_ref
from repro.launch.resilience import TenantQuarantined
from repro.launch.serve import VideoSearchConfig, VideoSearchServer


def _kernels(rng, O=2, C=1, kh=3, kw=4, kt=3):
    return jnp.asarray(rng.randn(O, C, kh, kw, kt).astype(np.float32))


def _scores(rng, B=2, O=3, L=700, ties=True):
    v = rng.randn(B, O, L).astype(np.float32)
    if ties:
        # force exact duplicates so the index tie-break is exercised
        v[..., 1::7] = v[..., 0::7][..., : v[..., 1::7].shape[-1]]
    return jnp.asarray(v)


# -- kernel vs oracle ---------------------------------------------------------


@pytest.mark.parametrize("k", [1, 4])
def test_topk_readout_matches_sort_oracle(k, rng):
    """Pallas (multi-tile), dense jnp, and the lexsort ref all agree
    bitwise — including under deliberate score ties, where the earliest
    global position must win."""
    vals = _scores(rng)
    gidx = jnp.arange(vals.shape[-1], dtype=jnp.int32)
    s_ref, i_ref = stmul_ref.topk_readout_ref(vals, gidx, k)
    for use_pallas in (False, True):
        s, ix = stmul_ops.topk_readout(
            vals, gidx, k, use_pallas=use_pallas,
            # small tiles force the multi-tile hierarchical merge
            block_o=2 if use_pallas else None,
            block_l=128 if use_pallas else None,
        )
        assert np.array_equal(np.asarray(s), np.asarray(s_ref))
        assert np.array_equal(np.asarray(ix), np.asarray(i_ref))


def test_topk_k1_is_first_occurrence_argmax(rng):
    """k = 1 reproduces jnp.argmax's first-occurrence rule exactly."""
    vals = _scores(rng, L=300)
    gidx = jnp.arange(vals.shape[-1], dtype=jnp.int32)
    s, ix = stmul_ops.topk_readout(vals, gidx, 1, use_pallas=False)
    assert np.array_equal(
        np.asarray(ix[..., 0]), np.asarray(jnp.argmax(vals, axis=-1))
    )
    assert np.array_equal(
        np.asarray(s[..., 0]), np.asarray(jnp.max(vals, axis=-1))
    )


def test_topk_readout_tile_knob_is_bitwise_neutral(rng):
    """Every readout tile configuration selects identically — the knob
    trades launch shape, never results (the kernels_bench sweep's
    precondition)."""
    vals = _scores(rng, L=1100)
    gidx = jnp.arange(vals.shape[-1], dtype=jnp.int32)
    base = stmul_ops.topk_readout(vals, gidx, 3, use_pallas=False)
    for bo, bl in [(1, 128), (2, 256), (8, 512), (4, 2048)]:
        s, ix = stmul_ops.topk_readout(
            vals, gidx, 3, use_pallas=True, block_o=bo, block_l=bl
        )
        assert np.array_equal(np.asarray(s), np.asarray(base[0])), (bo, bl)
        assert np.array_equal(np.asarray(ix), np.asarray(base[1])), (bo, bl)


@pytest.mark.parametrize("rows", [9, 36, 27])
def test_topk_readout_on_folded_rows_matches_topk_select(rows, rng):
    """The engine folds stream rows and kernels into one (B·n_out) axis:
    over such an axis of a length that is not a multiple of the 8-row
    tile, the Pallas readout equals ``topk_select`` bitwise."""
    vals = _scores(rng, B=1, O=rows, L=900)
    gidx = jnp.arange(vals.shape[-1], dtype=jnp.int32)
    want = stmul_kernel.topk_select(
        vals, jnp.broadcast_to(gidx, vals.shape), 3
    )
    got = stmul_ops.topk_readout(vals, gidx, 3, use_pallas=True,
                                 block_l=256)
    assert got[0].shape == (1, rows, 3)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


# -- associative merge properties --------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    k=st.sampled_from([1, 2, 5]),
    n_cuts=st.integers(min_value=1, max_value=6),
    perm_seed=st.integers(min_value=0, max_value=2**16),
)
def test_topk_merge_rechunk_and_permutation_invariant(
    seed, k, n_cuts, perm_seed
):
    """The property the streaming engine relies on: splitting the score
    axis at arbitrary points, reducing each segment independently, and
    merging the per-segment states *in any order* is bitwise the one-shot
    top-k.  (Total selection order ⇒ hierarchical selection is exact.)"""
    r = np.random.RandomState(seed)
    L = 257
    vals = jnp.asarray(r.randn(2, 2, L).astype(np.float32))
    gidx = jnp.arange(L, dtype=jnp.int32)
    one_shot = stmul_ops.topk_readout(vals, gidx, k, use_pallas=False)

    cuts = sorted(set(r.randint(1, L, size=n_cuts).tolist()))
    bounds = [0] + cuts + [L]
    states = [
        stmul_ops.topk_readout(
            vals[..., a:b], gidx[a:b], k, use_pallas=False
        )
        for a, b in zip(bounds[:-1], bounds[1:])
    ]
    order = np.random.RandomState(perm_seed).permutation(len(states))
    merged = stmul_ops.merge_topk([states[i] for i in order], k)
    assert np.array_equal(np.asarray(merged[0]), np.asarray(one_shot[0]))
    assert np.array_equal(np.asarray(merged[1]), np.asarray(one_shot[1]))


def test_topk_k_geq_candidates_pads_with_sentinel():
    """K larger than the candidate pool (K ≥ ties included): exhausted
    slots carry −inf / the empty sentinel, and merging exhausted states
    stays exact instead of resurrecting knocked-out candidates."""
    vals = jnp.asarray([[[3.0, 3.0, 1.0]]], jnp.float32)  # tie at the top
    gidx = jnp.arange(3, dtype=jnp.int32)
    s, ix = stmul_ops.topk_readout(vals, gidx, 5, use_pallas=False)
    assert np.array_equal(
        np.asarray(s[0, 0]), [3.0, 3.0, 1.0, -np.inf, -np.inf]
    )
    assert np.array_equal(
        np.asarray(ix[0, 0]), [0, 1, 2, TOPK_EMPTY_IDX, TOPK_EMPTY_IDX]
    )
    # hierarchical: merging two exhausted single-element states == dense
    a = stmul_ops.topk_readout(vals[..., :1], gidx[:1], 5, use_pallas=False)
    b = stmul_ops.topk_readout(vals[..., 1:], gidx[1:], 5, use_pallas=False)
    ms, mi = stmul_ops.merge_topk([a, b], 5)
    assert np.array_equal(np.asarray(ms), np.asarray(s))
    assert np.array_equal(np.asarray(mi), np.asarray(ix))


def test_topk_nan_poisons_row_and_only_that_row():
    """A NaN score saturates every slot of its (row, kernel) — scores
    NaN, indices sentinel — identically on the dense, tiled-Pallas, and
    merged paths, while other rows reduce untouched.  This is what the
    serving guard's quarantine keys on."""
    vals = np.random.RandomState(0).randn(1, 2, 400).astype(np.float32)
    vals[0, 1, 37] = np.nan
    vals = jnp.asarray(vals)
    gidx = jnp.arange(400, dtype=jnp.int32)
    clean = stmul_ops.topk_readout(vals[:, :1], gidx, 3, use_pallas=False)
    for use_pallas in (False, True):
        s, ix = stmul_ops.topk_readout(
            vals, gidx, 3, use_pallas=use_pallas,
            block_l=128 if use_pallas else None,
        )
        assert np.isnan(np.asarray(s[0, 1])).all()
        assert np.array_equal(np.asarray(ix[0, 1]), [TOPK_EMPTY_IDX] * 3)
        assert np.array_equal(np.asarray(s[0, 0]), np.asarray(clean[0][0, 0]))
        assert np.array_equal(np.asarray(ix[0, 0]), np.asarray(clean[1][0, 0]))


# -- engine: fused streaming vs the stitched-volume oracle --------------------


def _stitched_topk(vol, k):
    """Oracle: flatten the stitched (B, O, H', W', T') volume in the
    fused path's C-order and lexsort-select."""
    B, O, Hp, Wp, Tv = vol.shape
    flat = vol.reshape(B, O, -1).astype(jnp.float32)
    gidx = jnp.arange(Hp * Wp * Tv, dtype=jnp.int32)
    return stmul_ref.topk_readout_ref(flat, gidx, k)


def test_query_stream_fused_matches_stitched_paper_geometry(rng):
    """Acceptance: at the paper geometry (30×40×8 kernels, 60×80
    frames, physical fidelity), the fused streaming top-K — one-shot
    AND cursor-chunked — equals the stitched volume's reduction
    bitwise, and positions decode to the volume's argmax coordinates."""
    eng = QueryEngine(STHCConfig(fidelity=fid.physical(), osave_chunk_windows=2))
    g = eng.record(_kernels(rng, O=2, kh=30, kw=40, kt=8), (60, 80, 16))
    x = jnp.asarray(rng.rand(1, 1, 60, 80, 70).astype(np.float32))
    vol = eng.query_stream(g, x)
    det = eng.query_stream(g, x, readout_k=4)
    s_ref, i_ref = _stitched_topk(vol, 4)
    assert np.array_equal(np.asarray(det.scores), np.asarray(s_ref))
    assert np.array_equal(np.asarray(det.index), np.asarray(i_ref))
    # chunked (constant-memory cursor) == one-shot, bitwise
    det_c = eng.query_stream(g, x, readout_k=4, max_buffer_windows=2)
    assert np.array_equal(np.asarray(det_c.scores), np.asarray(det.scores))
    assert np.array_equal(np.asarray(det_c.index), np.asarray(det.index))
    # decoded positions point at the volume's peak
    t, h, w = det.positions()
    b, o = 0, 1
    assert np.asarray(vol)[
        b, o, int(h[b, o, 0]), int(w[b, o, 0]), int(t[b, o, 0])
    ] == np.asarray(det.peak_scores())[b, o]


def test_query_stream_many_fused_dedup_bf16_matches_stitched(rng):
    """Acceptance: the pooled fused path — clip-dedup union-slice rows,
    bf16 grating storage, bounded-memory chunking — is bitwise the
    stitched pooled volumes' reduction, per request."""
    eng = QueryEngine(
        STHCConfig(
            fidelity=fid.physical(),
            osave_chunk_windows=2,
            grating_dtype="bfloat16",
            keep_stacked=False,
        )
    )
    g1 = eng.record(_kernels(rng, O=2), (20, 24, 11))
    g2 = eng.record(_kernels(rng, O=3), (20, 24, 11))
    x = jnp.asarray(rng.rand(1, 1, 20, 24, 53).astype(np.float32))
    reqs = [(g1, x), (g2, x)]
    keys = [("clip",), ("clip",)]  # same content: dedup onto one row
    vols = eng.query_stream_many(reqs, clip_keys=keys)
    dets = eng.query_stream_many(reqs, clip_keys=keys, readout_k=3)
    dets_c = eng.query_stream_many(
        reqs, clip_keys=keys, readout_k=3, max_buffer_windows=2
    )
    for det, det_c, vol in zip(dets, dets_c, vols):
        assert isinstance(det, TopKDetections)
        s_ref, i_ref = _stitched_topk(vol, 3)
        assert np.array_equal(np.asarray(det.scores), np.asarray(s_ref))
        assert np.array_equal(np.asarray(det.index), np.asarray(i_ref))
        assert np.array_equal(np.asarray(det_c.scores), np.asarray(s_ref))
        assert np.array_equal(np.asarray(det_c.index), np.asarray(i_ref))


def test_fused_streaming_matches_own_stitched_volume_per_backend(rng):
    """Each backend's fused readout is bitwise its *own* stitched
    volume's reduction.  (use_pallas swaps the MAC kernel too, so the
    volumes — and hence the detections — legitimately differ across
    backends in last-bit rounding; the fused-vs-stitched equality is
    the per-backend invariant.)"""
    ker = _kernels(rng)
    x = jnp.asarray(rng.rand(2, 1, 12, 12, 40).astype(np.float32))
    for use_pallas in (False, True):
        eng = QueryEngine(
            STHCConfig(use_pallas=use_pallas, osave_chunk_windows=2)
        )
        g = eng.record(ker, (12, 12, 8))
        det = eng.query_stream(g, x, readout_k=2)
        s_ref, i_ref = _stitched_topk(eng.query_stream(g, x), 2)
        assert np.array_equal(np.asarray(det.scores), np.asarray(s_ref))
        assert np.array_equal(np.asarray(det.index), np.asarray(i_ref))


# -- serving: one readout across every entry point ---------------------------


def _server(kernels, **cfg_kw):
    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2, **cfg_kw)
    srv = VideoSearchServer(frame_hw=(12, 12), cfg=cfg)
    for name, ker in kernels.items():
        srv.add_tenant(name, ker)
    return srv


@pytest.fixture
def tenant_kernels(rng):
    return {n: _kernels(rng) for n in ("a", "b")}


@pytest.fixture
def clips(rng):
    return [
        jnp.asarray(rng.rand(1, 1, 12, 12, 40).astype(np.float32))
        for _ in range(2)
    ]


def test_serve_readout_identical_across_entry_points(tenant_kernels, clips):
    """Regression for the readout-path divergence: search,
    search_batch(pooled), search_batch(sequential), fused and stitched
    all report bitwise-identical scores and peak frames."""
    fused = _server(tenant_kernels)
    stitched = _server(tenant_kernels, fused_readout=False)
    reqs = [("a", clips[0]), ("b", clips[0]), ("a", clips[1])]
    ref = fused.search_batch(reqs)
    variants = [
        fused.search_batch(reqs, pooled=False),
        stitched.search_batch(reqs),
        stitched.search_batch(reqs, pooled=False),
    ]
    for outs in variants:
        for o, r in zip(outs, ref):
            assert np.array_equal(o["scores"], r["scores"])
            assert np.array_equal(o["peak_frame"], r["peak_frame"])
    # the single-request entry point is exactly a one-request batch
    one = fused.search(clips[0], "a")
    assert np.array_equal(one["scores"], ref[0]["scores"])
    assert np.array_equal(one["peak_frame"], ref[0]["peak_frame"])
    one_s = stitched.search(clips[0], "a")
    assert np.array_equal(one_s["scores"], ref[0]["scores"])
    assert np.array_equal(one_s["peak_frame"], ref[0]["peak_frame"])


def test_serve_return_volume_forces_stitched_and_agrees(tenant_kernels, clips):
    """return_volume=True serves the stitched oracle path: the volume's
    own max/argmax reproduce the fused scores bitwise."""
    srv = _server(tenant_kernels)
    fused_out = srv.search(clips[0], "a")
    out = srv.search(clips[0], "a", return_volume=True)
    vol = np.asarray(out["volume"])
    assert vol.ndim == 5
    flat = vol.reshape(vol.shape[0], vol.shape[1], -1)
    assert np.array_equal(flat.max(-1), out["scores"])
    assert np.array_equal(out["scores"], fused_out["scores"])
    assert np.array_equal(out["peak_frame"], fused_out["peak_frame"])


def test_serve_topk_results(tenant_kernels, clips):
    """readout_topk > 1 adds per-slot scores/frames; slot 0 is the
    peak, slots descend, and they match the volume's k best."""
    srv = _server(tenant_kernels, readout_topk=3)
    out = srv.search(clips[0], "a")
    assert out["topk_scores"].shape[-1] == 3
    assert np.array_equal(out["topk_scores"][..., 0], out["scores"])
    assert np.array_equal(out["topk_frames"][..., 0], out["peak_frame"])
    assert (np.diff(out["topk_scores"], axis=-1) <= 0).all()
    vol = np.asarray(
        srv.search(clips[0], "a", return_volume=True)["volume"]
    )
    best = -np.sort(-vol.reshape(vol.shape[0], vol.shape[1], -1), -1)[..., :3]
    assert np.array_equal(best, out["topk_scores"])


def test_serve_fused_nan_quarantine_isolates_row(tenant_kernels, clips):
    """guard_scores on the fused path: a NaN anywhere in one request's
    (never-materialized) volume propagates into its peak slot and
    quarantines exactly that request; its pooled peers deliver bitwise
    the clean-run results."""
    srv = _server(tenant_kernels)
    clean = srv.search_batch([("a", clips[0]), ("b", clips[1])])
    bad = np.array(clips[0])
    bad[0, 0, 5, 5, 20] = np.nan
    outs = srv.search_batch([("a", jnp.asarray(bad)), ("b", clips[1])])
    assert isinstance(outs[0], TenantQuarantined)
    assert outs[0].tenant == "a"
    assert isinstance(outs[1], dict)
    assert np.array_equal(outs[1]["scores"], clean[1]["scores"])
    assert np.array_equal(outs[1]["peak_frame"], clean[1]["peak_frame"])
    assert srv.metrics()["quarantined"] == 1


def test_serve_guard_off_delivers_nan_scores(tenant_kernels, clips):
    """guard_scores=False: the fused path reports the NaN row as-is
    (slot saturation, sentinel positions) instead of quarantining."""
    srv = _server(tenant_kernels, guard_scores=False)
    bad = np.array(clips[0])
    bad[0, 0, 5, 5, 20] = np.nan
    out = srv.search(jnp.asarray(bad), "a")
    assert np.isnan(out["scores"]).all()
