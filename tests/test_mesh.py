"""Device-mesh sharded serving: the pooled grating arena over the model
axis, stream fan-out over the data axis, bitwise-equal to single-device.

Multi-device tests need 8 host devices (CI's mesh-smoke leg sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before any jax
import); on a plain 1-device checkout they skip.  Setting
``REPRO_REQUIRE_MESH=1`` converts the skip into a hard failure, so the
CI leg can assert the suite actually ran un-skipped.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core import fidelity as fid
from repro.core.sthc import STHC, STHCConfig
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import VideoSearchConfig, VideoSearchServer

_ENOUGH = jax.device_count() >= 8
_REQUIRED = os.environ.get("REPRO_REQUIRE_MESH") == "1"
needs_mesh = pytest.mark.skipif(
    not _ENOUGH and not _REQUIRED,
    reason="needs 8 host devices — set "
    'XLA_FLAGS="--xla_force_host_platform_device_count=8" before jax '
    "imports (REPRO_REQUIRE_MESH=1 makes this a failure instead)",
)


def _kernels(seed, O=3, C=1, kh=7, kw=9, kt=4):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(O, C, kh, kw, kt).astype(np.float32))


def _clips(seed, B=2, C=1, H=20, W=24, T=40):
    rng = np.random.RandomState(100 + seed)
    return jnp.asarray(rng.rand(B, C, H, W, T).astype(np.float32))


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(bool(jnp.all(x == y)) for x, y in zip(la, lb))


def _requests(eng, T=40):
    ks = [_kernels(i, O=o) for i, o in enumerate((3, 5, 2, 4))]
    xs = [_clips(i, B=b, T=T) for i, b in enumerate((2, 1, 3, 2))]
    gs = [eng.record(k, x.shape[-3:]) for k, x in zip(ks, xs)]
    return list(zip(gs, xs))


def _engine(**over):
    cfg = dict(fidelity=fid.physical(), osave_chunk_windows=2)
    cfg.update(over)
    return STHC(STHCConfig(**cfg)).engine


# ---------------------------------------------------------------------------
# bitwise equality: sharded == single-device
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("shape", [(1, 1), (8, 1), (1, 8), (2, 4)])
def test_stream_volumes_bitwise(shape):
    eng = _engine()
    reqs = _requests(eng)
    ref = eng.query_stream_many(reqs, dedup=True)
    got = eng.query_stream_many(reqs, dedup=True, mesh=make_local_mesh(*shape))
    assert _bitwise(ref, got)


@needs_mesh
def test_stream_fused_topk_bitwise():
    eng = _engine()
    reqs = _requests(eng)
    mesh = make_local_mesh(2, 4)
    ref = eng.query_stream_many(reqs, dedup=True, readout_k=3)
    got = eng.query_stream_many(reqs, dedup=True, readout_k=3, mesh=mesh)
    assert _bitwise(
        [(d.scores, d.index) for d in ref],
        [(d.scores, d.index) for d in got],
    )


@needs_mesh
def test_shared_stream_dedup_bitwise():
    """All tenants searching one content-equal clip: dedup collapses to
    unique physical rows on the mesh too, and scores stay bitwise."""
    eng = _engine()
    gs = [g for g, _ in _requests(eng)]
    shared = _clips(9)
    reqs = [(g, shared) for g in gs]
    mesh = make_local_mesh(2, 4)
    ref = eng.query_stream_many(reqs, dedup=True, readout_k=2)
    got = eng.query_stream_many(reqs, dedup=True, readout_k=2, mesh=mesh)
    assert _bitwise(
        [(d.scores, d.index) for d in ref],
        [(d.scores, d.index) for d in got],
    )


@needs_mesh
@pytest.mark.parametrize("readout_k", [None, 2])
def test_chunked_cursor_bitwise(readout_k):
    """Bounded-memory StreamCursor segments ride the sharded driver."""
    eng = _engine()
    reqs = _requests(eng)
    mesh = make_local_mesh(2, 4)
    kw = dict(dedup=True, max_buffer_windows=3, readout_k=readout_k)
    ref = eng.query_stream_many(reqs, **kw)
    got = eng.query_stream_many(reqs, mesh=mesh, **kw)
    if readout_k is None:
        assert _bitwise(ref, got)
    else:
        assert _bitwise(
            [(d.scores, d.index) for d in ref],
            [(d.scores, d.index) for d in got],
        )


@needs_mesh
def test_bf16_storage_bitwise():
    eng = _engine(grating_dtype="bfloat16")
    reqs = _requests(eng)
    mesh = make_local_mesh(2, 4)
    ref = eng.query_stream_many(reqs, dedup=True, readout_k=2)
    got = eng.query_stream_many(reqs, dedup=True, readout_k=2, mesh=mesh)
    assert _bitwise(
        [(d.scores, d.index) for d in ref],
        [(d.scores, d.index) for d in got],
    )


@needs_mesh
def test_pallas_grouped_kernel_bitwise():
    eng = _engine(use_pallas=True)
    reqs = _requests(eng)
    mesh = make_local_mesh(2, 4)
    ref = eng.query_stream_many(reqs, dedup=True)
    got = eng.query_stream_many(reqs, dedup=True, mesh=mesh)
    assert _bitwise(ref, got)


@needs_mesh
def test_query_many_oneshot_bitwise():
    """One-shot clips — each a stream of one window — are answered
    bitwise alike on the mesh and on one device."""
    eng = _engine()
    ks = [_kernels(i, O=o) for i, o in enumerate((3, 5, 2, 4))]
    xs = [_clips(i, B=b, T=10) for i, b in enumerate((2, 1, 3, 2))]
    gs = [eng.record(k, x.shape[-3:]) for k, x in zip(ks, xs)]
    reqs = list(zip(gs, xs))
    assert eng.stream_plan_for(gs[0], 10).n_blocks == 1
    ref = eng.query_stream_many(reqs, dedup=True)
    got = eng.query_stream_many(reqs, dedup=True, mesh=make_local_mesh(2, 4))
    assert _bitwise(ref, got)


@needs_mesh
@pytest.mark.parametrize("use_pallas", [False, True])
def test_new_composition_reuses_mesh_arena_and_program(use_pallas):
    """The mesh path reads the resident arena: a batch of a tenant
    composition it has not seen packs no arena and traces no program,
    and its answers stay bitwise equal to one device."""
    eng = _engine(use_pallas=use_pallas)
    gs = [g for g, _ in _requests(eng)]
    eng.set_resident(gs)
    streams = [_clips(10 + i, B=1) for i in range(3)]
    first = [(gs[0], streams[0]), (gs[1], streams[1]), (gs[1], streams[2])]
    second = [(gs[3], streams[2]), (gs[2], streams[0]), (gs[0], streams[1])]
    mesh = make_local_mesh(2, 4)
    refs = [eng.query_stream_many(b, readout_k=2) for b in (first, second)]
    got = [eng.query_stream_many(first, readout_k=2, mesh=mesh)]
    warm = eng.pool_stats()
    got.append(eng.query_stream_many(second, readout_k=2, mesh=mesh))
    after = eng.pool_stats()
    assert after["arena_builds"] == warm["arena_builds"] == 2
    assert after["stream_traces"] == warm["stream_traces"]
    assert after["dispatches"] == warm["dispatches"] + 1
    for ref, out in zip(refs, got):
        assert _bitwise(
            [(d.scores, d.index) for d in ref],
            [(d.scores, d.index) for d in out],
        )


@needs_mesh
def test_serving_end_to_end_mesh():
    """A mesh-configured server serves bitwise-identical detections."""
    k = _kernels(0, O=2, kh=3, kw=4, kt=3)
    clip = _clips(0, B=1, H=12, W=12, T=20)
    cfg = VideoSearchConfig(window_frames=8)
    ref_srv = VideoSearchServer(k, (12, 12), cfg=cfg)
    mesh_srv = VideoSearchServer(
        k, (12, 12), cfg=VideoSearchConfig(window_frames=8, mesh_shape=(2, 4))
    )
    assert mesh_srv.mesh is not None and mesh_srv.mesh.size == 8
    ref_out = ref_srv.search(clip)
    got_out = mesh_srv.search(clip)
    assert _bitwise(
        jnp.asarray(ref_out["scores"]), jnp.asarray(got_out["scores"])
    )
    m = mesh_srv.metrics()["mesh"]
    assert m == {"shape": {"data": 2, "model": 4}, "devices": 8}
    assert ref_srv.metrics()["mesh"] is None


# ---------------------------------------------------------------------------
# shard-tiled arena packing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("align", [1, 2, 4])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_pool_packing_alignment(align, shards):
    """Every member slot starts on the align grid and lives entirely
    inside one shard tile; tiles are equal-height."""
    eng = _engine()
    widths = (3, 5, 2, 4, 1, 7)
    ks = [_kernels(i, O=o) for i, o in enumerate(widths)]
    gs = [eng.record(k, (20, 24, 10)) for k in ks]
    pool = engine_mod._build_pool(gs, align, shards)
    assert pool.shards == shards
    rows = int(pool.re.shape[0])
    assert rows == shards * pool.shard_rows
    assert pool.shard_rows % align == 0 or align == 1
    for o0, g in zip(pool.o_start, gs):
        assert o0 % align == 0
        if shards > 1:
            tile0 = o0 // pool.shard_rows
            tile1 = (o0 + g.n_out - 1) // pool.shard_rows
            assert tile0 == tile1, "slot straddles a shard tile"
        # arena rows hold the member's planes verbatim
        re, im = g.planes
        assert bool(jnp.all(pool.re[o0 : o0 + g.n_out] == re))
        assert bool(jnp.all(pool.im[o0 : o0 + g.n_out] == im))


def test_bin_members_deterministic_least_loaded():
    bin_of, shard_rows = engine_mod._bin_members([5, 3, 4, 2], 2)
    # greedy least-loaded: 5->t0, 3->t1, 4->t1 (load 3<5), 2->t0
    assert bin_of == [0, 1, 1, 0]
    assert shard_rows == 7
    # ties break to the lowest tile index — deterministic
    bin_of, _ = engine_mod._bin_members([1, 1, 1, 1], 4)
    assert bin_of == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------


def test_make_local_mesh_raises_on_short_device_count():
    if jax.device_count() >= 64:
        pytest.skip("environment unexpectedly has >= 64 devices")
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_local_mesh(8, 8)


def test_short_device_error_names_accelerator_platform():
    """On an accelerator host the fix is a larger slice, not XLA_FLAGS."""
    from types import SimpleNamespace

    from repro.launch import mesh as mesh_lib

    chips = [SimpleNamespace(platform="tpu")] * 4
    msg = str(mesh_lib._short_devices("mesh (1, 8)", 8, chips))
    assert "found 4 tpu device(s)" in msg
    assert "XLA_FLAGS" not in msg


def test_make_local_mesh_rejects_bad_axes():
    with pytest.raises(ValueError, match="mesh axes"):
        make_local_mesh(0, 2)


@pytest.mark.parametrize(
    "bad", [(0, 1), (2,), (2, 2, 2), ("2", "4"), (True, 2), 8]
)
def test_config_rejects_bad_mesh_shape(bad):
    with pytest.raises((ValueError, TypeError)):
        VideoSearchConfig(mesh_shape=bad)


def test_config_accepts_mesh_shape_list():
    cfg = VideoSearchConfig(mesh_shape=[2, 4])
    assert cfg.mesh_shape == (2, 4)
    assert VideoSearchConfig().mesh_shape is None


@needs_mesh
def test_mesh_smoke_marker_ran():
    """Sentinel for the CI mesh leg: if this test reports as passed, the
    multi-device tests above ran un-skipped."""
    assert jax.device_count() >= 8 or _REQUIRED
    if _REQUIRED:
        assert _ENOUGH, (
            "REPRO_REQUIRE_MESH=1 but only "
            f"{jax.device_count()} device(s) — the CI leg must export "
            "XLA_FLAGS before any jax import"
        )
