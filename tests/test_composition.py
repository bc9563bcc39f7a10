"""A batch's composition is runtime data on the pooled stream path: any
mix of resident tenants is answered right by programs compiled once per
(pool group, row bucket), from one resident arena per pool group."""

import importlib.util
import pathlib
import sys
import threading
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fidelity as fid
from repro.core.engine import QueryEngine, _row_bucket, _span_layout
from repro.kernels.stmul import kernel as stmul_kernel
from repro.core.sthc import STHCConfig
from repro.launch.serve import VideoSearchConfig, VideoSearchServer

FRAME_HW = (12, 16)
SIGNAL = (12, 16, 8)  # window geometry the gratings are recorded at
N_TENANTS = 12
FRAMES = 24
# the pooled program and query_stream are two differently fused float32
# programs over the same arithmetic: scores agree to a few ulps of the
# request's largest score, not bitwise
RTOL = 1e-5


def _engines(use_pallas: bool, **cfg):
    """(the pooled engine, per-tenant engines, per-tenant gratings): 12
    seeded tenants alternating ideal and physical, as the served cell
    alternates them by popularity rank."""
    rng = np.random.RandomState(11)
    ideal = QueryEngine(STHCConfig(fidelity=fid.ideal(), use_pallas=use_pallas,
                                   osave_chunk_windows=2, **cfg))
    phys = QueryEngine(STHCConfig(fidelity=fid.physical(),
                                  use_pallas=use_pallas,
                                  osave_chunk_windows=2, **cfg))
    engines, gratings = [], []
    for t in range(N_TENANTS):
        eng = ideal if t % 2 == 0 else phys
        k = jnp.asarray(rng.randn(3, 1, 4, 6, 3).astype(np.float32))
        engines.append(eng)
        gratings.append(eng.record(k, SIGNAL))
    ideal.set_resident(gratings)
    return ideal, engines, gratings


def _streams(n: int, seed: int = 3) -> list[np.ndarray]:
    rng = np.random.RandomState(seed)
    return [rng.rand(1, 1, *FRAME_HW, FRAMES).astype(np.float32)
            for _ in range(n)]


def _zipf(rng, n: int) -> list[int]:
    rank = np.arange(1, N_TENANTS + 1, dtype=np.float64)
    p = rank ** -1.1
    return [int(t) for t in rng.choice(N_TENANTS, size=n, p=p / p.sum())]


def _compositions(rng, streams):
    """30 Zipf-drawn batches of (tenant, clip) requests.  Streams are
    drawn with replacement from a small pool, so some batches share a
    stream between tenants (dedup union spans); the first batches pin a
    hot tenant twice on two streams, a hot tenant stacked twice in one
    request, and a single-fidelity batch."""
    out = [
        [(0, streams[0]), (0, streams[1]), (1, streams[2])],
        [(0, np.concatenate([streams[3], streams[4]])), (3, streams[5])],
        [(0, streams[6]), (2, streams[7]), (4, streams[6]), (8, streams[0])],
    ]
    while len(out) < 30:
        tenants = _zipf(rng, int(rng.randint(1, 9)))
        out.append([(t, streams[int(rng.randint(len(streams)))])
                    for t in tenants])
    return out


def _check(engines, gratings, batch, dets):
    for (t, x), det in zip(batch, dets):
        vol = np.asarray(engines[t].query_stream(gratings[t], jnp.asarray(x)))
        flat = vol.reshape(vol.shape[0], vol.shape[1], -1)
        peak = flat.max(-1)
        scale = np.abs(flat).max(-1)
        s = np.asarray(det.scores)[..., 0]
        i = np.asarray(det.index)[..., 0]
        assert s.shape == peak.shape
        assert np.all(np.abs(s - peak) <= RTOL * scale), (t, s, peak)
        # the served position holds the peak, up to the same tolerance
        # (two positions within it of each other are a tie)
        at = np.take_along_axis(flat, i[..., None], -1)[..., 0]
        assert np.all(at >= peak - RTOL * scale), (t, at, peak)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_zipf_compositions_match_each_request_alone(use_pallas):
    """30 compositions through the runtime path: every request's top-1
    equals its own query_stream's peak and position."""
    pooled, engines, gratings = _engines(use_pallas)
    streams = _streams(8)
    rng = np.random.RandomState(5)
    for batch in _compositions(rng, streams):
        dets = pooled.query_stream_many(
            [(gratings[t], x) for t, x in batch], readout_k=1
        )
        _check(engines, gratings, batch, dets)
    stats = pooled.pool_stats()
    assert stats["arena_builds"] == 2  # one per pool group, never again
    assert stats["rows_saved"] > 0  # some batches shared a stream


def _dense(storage: str) -> tuple[QueryEngine, QueryEngine]:
    return tuple(
        QueryEngine(STHCConfig(fidelity=f, osave_chunk_windows=2,
                               grating_dtype=storage))
        for f in (fid.ideal(), fid.physical())
    )


@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_lane_arena_answers_equal_dense_query_stream(storage):
    """The Pallas path stores its resident arenas as lane planes and
    reads the MAC's output in that layout: for every row count 1–8 of
    a batch over both fidelities, each request's top-1 equals its own
    dense ``query_stream`` — the score to a few ulps, the index
    bitwise — and every dispatch is counted as read in the layout."""
    pooled, _, gratings = _engines(True, grating_dtype=storage)
    dense = _dense(storage)
    streams = _streams(8)
    for n in range(1, 9):
        batch = [((5 * r + n) % N_TENANTS, streams[r]) for r in range(n)]
        dets = pooled.query_stream_many(
            [(gratings[t], x) for t, x in batch], readout_k=1
        )
        for (t, x), det in zip(batch, dets):
            vol = np.asarray(dense[t % 2].query_stream(gratings[t],
                                                       jnp.asarray(x)))
            flat = vol.reshape(vol.shape[0], vol.shape[1], -1)
            scale = np.abs(flat).max(-1)
            s = np.asarray(det.scores)[..., 0]
            assert np.all(np.abs(s - flat.max(-1)) <= RTOL * scale), (n, t)
            assert np.array_equal(np.asarray(det.index)[..., 0],
                                  flat.argmax(-1)), (n, t)
    for arena in pooled._arenas.values():
        assert arena.pool.re.ndim == 4  # (rows, C, FTr·Hp, Wp)
        assert arena.pool.re.dtype == jnp.dtype(storage)
    stats = pooled.pool_stats()
    assert stats["native_layout_dispatches"] == stats["dispatches"] > 0


def test_chunked_cursor_on_lane_arena_equals_one_shot():
    """Streams fed through the cursor one window at a time ride the same
    lane-plane programs, and their merged states equal the one-shot
    pass bitwise."""
    pooled, _, gratings = _engines(True)
    streams = _streams(5)
    reqs = [(gratings[t], x) for t, x in zip((0, 1, 2, 4, 7), streams)]
    one = pooled.query_stream_many(reqs, readout_k=2)
    before = pooled.pool_stats()["native_layout_dispatches"]
    chunked = pooled.query_stream_many(reqs, readout_k=2,
                                       max_buffer_windows=1)
    for a, b in zip(one, chunked):
        assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
        assert np.array_equal(np.asarray(a.index), np.asarray(b.index))
    # one count per pool-group dispatch, however many segments it took
    assert pooled.pool_stats()["native_layout_dispatches"] == before + 2


def test_native_layout_counter_stays_zero_on_5d_arenas():
    """Only the dense path keeps 5-D arenas, and none of its dispatches
    counts as read in the lane layout; the Pallas mesh path reads a
    lane-plane arena, and every one of its dispatches counts."""
    from repro.launch.mesh import make_local_mesh

    streams = _streams(3)
    dense, _, gratings = _engines(False)
    dense.query_stream_many([(gratings[0], streams[0])], readout_k=1)
    dense.query_stream_many(
        [(gratings[0], streams[0]), (gratings[2], streams[1])],
        readout_k=1, mesh=make_local_mesh(1, 1),
    )
    stats = dense.pool_stats()
    assert stats["dispatches"] == 2
    assert stats["native_layout_dispatches"] == 0
    assert all(a.pool.re.ndim == 5 for a in dense._arenas.values())
    pallas, _, gratings = _engines(True)
    pallas.query_stream_many(
        [(gratings[0], streams[0]), (gratings[2], streams[1])],
        readout_k=1, mesh=make_local_mesh(1, 1),
    )
    stats = pallas.pool_stats()
    assert stats["native_layout_dispatches"] == stats["dispatches"] == 1
    assert all(a.pool.re.ndim == 4 for a in pallas._arenas.values())


def test_whole_state_slices_equal_device_slices():
    """``whole_state`` hands back the group state and each request's
    rows and kernels in it; slicing it equals the split answer."""
    pooled, _, gratings = _engines(False)
    streams = _streams(4)
    reqs = [(gratings[0], streams[0]), (gratings[2], streams[1]),
            (gratings[1], streams[2]), (gratings[0], streams[3])]
    split = pooled.query_stream_many(reqs, readout_k=2)
    whole = pooled.query_stream_many(reqs, readout_k=2, whole_state=True)
    for a, b in zip(split, whole):
        assert b.scores.shape[0] == _row_bucket(b.scores.shape[0])
        host = np.asarray(b.scores)[b.rows, b.kernels]
        assert np.array_equal(np.asarray(a.scores), host)
        assert np.array_equal(np.asarray(a.index),
                              np.asarray(b.index)[b.rows, b.kernels])


def test_stream_traces_stop_growing_once_buckets_are_warm():
    pooled, _, gratings = _engines(False)
    streams = _streams(8)
    by_group = [list(range(0, N_TENANTS, 2)), list(range(1, N_TENANTS, 2))]
    for tenants in by_group:  # rows 1..8: every bucket of each group
        for n in range(1, 9):
            pooled.query_stream_many(
                [(gratings[tenants[r % len(tenants)]], streams[r])
                 for r in range(n)],
                readout_k=1,
            )
    warm = pooled.stream_traces
    assert warm == 2 * 6  # buckets 1, 2, 3, 4, 6, 8 per pool group
    rng = np.random.RandomState(9)
    for _ in range(20):
        tenants = _zipf(rng, 8)
        order = rng.permutation(8)
        pooled.query_stream_many(
            [(gratings[t], streams[int(s)]) for t, s in zip(tenants, order)],
            readout_k=1,
        )
    assert pooled.stream_traces == warm
    assert pooled.pool_stats()["stream_traces"] == warm


def test_undeclared_grating_is_admitted_by_one_rebuild():
    pooled, engines, gratings = _engines(False)
    extra = engines[0].record(
        jnp.asarray(np.random.RandomState(2).randn(3, 1, 4, 6, 3)
                    .astype(np.float32)),
        SIGNAL,
    )
    engines.append(engines[0])
    gratings.append(extra)
    streams = _streams(2)
    batch = [(N_TENANTS, streams[0]), (0, streams[1])]
    for _ in range(2):
        dets = pooled.query_stream_many(
            [(gratings[t], x) for t, x in batch], readout_k=1
        )
        _check(engines, gratings, batch, dets)
    assert pooled.pool_stats()["arena_builds"] == 1


def test_concurrent_batches_and_declarations_stay_right():
    """Eight threads serve batches while another declares the residents
    in two orders over and over, so arenas are packed again under their
    feet: every answer stays right and every thread finishes in time."""
    pooled, engines, gratings = _engines(False)
    streams = _streams(8)
    batches = []
    for seed in range(8):
        rng = np.random.RandomState(seed)
        batches.append([
            [(t, streams[int(rng.randint(8))]) for t in _zipf(rng, 4)]
            for _ in range(4)
        ])
    # compile every program and reference before the threads start
    for runs in batches:
        for batch in runs:
            _check(engines, gratings, batch, pooled.query_stream_many(
                [(gratings[t], x) for t, x in batch], readout_k=1))
    errors: list = []
    stop = threading.Event()

    def serve(runs):
        try:
            for batch in runs:
                dets = pooled.query_stream_many(
                    [(gratings[t], x) for t, x in batch], readout_k=1
                )
                _check(engines, gratings, batch, dets)
        except Exception as exc:  # noqa: BLE001 — reported below
            errors.append(exc)

    def declare():
        while not stop.is_set():
            pooled.set_resident(gratings[::-1])
            pooled.set_resident(gratings)

    workers = [threading.Thread(target=serve, args=(r,)) for r in batches]
    declarer = threading.Thread(target=declare)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        declarer.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=300)
    finally:
        stop.set()
        declarer.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert not declarer.is_alive()
    assert not errors, errors


def _server(n: int) -> VideoSearchServer:
    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2,
                            cache_entries=16)
    server = VideoSearchServer(frame_hw=FRAME_HW, cfg=cfg)
    rng = np.random.RandomState(4)
    for t in range(n):
        server.add_tenant(
            f"t{t}", rng.randn(3, 1, 4, 6, 3).astype(np.float32),
            fidelity=fid.ideal() if t % 2 == 0 else fid.physical(),
        )
    return server


def _answers_right(server, requests):
    """Each pooled answer equals the tenant's own sequential search."""
    pooled = server.search_batch(requests)
    alone = server.search_batch(requests, pooled=False)
    for a, b in zip(pooled, alone):
        scale = np.abs(b["scores"]).max()
        assert np.all(np.abs(a["scores"] - b["scores"]) <= RTOL * scale)


def test_adding_or_removing_a_tenant_rebuilds_its_arena_once():
    server = _server(6)
    engine = server.sthc.engine
    streams = _streams(4)
    reqs = [("t0", streams[0]), ("t1", streams[1]), ("t2", streams[2])]
    _answers_right(server, reqs)
    builds = engine.pool_stats()["arena_builds"]
    assert builds == 2  # one arena per pool group
    _answers_right(server, reqs)
    assert engine.pool_stats()["arena_builds"] == builds
    # a new ideal tenant: only the ideal group's arena is packed again
    server.add_tenant(
        "t6", np.random.RandomState(8).randn(3, 1, 4, 6, 3).astype(np.float32),
        fidelity=fid.ideal(),
    )
    reqs.append(("t6", streams[3]))
    _answers_right(server, reqs)
    assert engine.pool_stats()["arena_builds"] == builds + 1
    _answers_right(server, reqs)
    assert engine.pool_stats()["arena_builds"] == builds + 1
    server.remove_tenant("t4")
    _answers_right(server, reqs)
    assert engine.pool_stats()["arena_builds"] == builds + 2


# -- slots at the pool group's kernel count ------------------------------------


def _nine_kernel_engines(n: int, **cfg):
    """(ideal engine, physical engine, gratings): ``n`` seeded tenants of
    9 kernels each — the paper's bank — alternating ideal and physical,
    all declared resident on the ideal engine."""
    rng = np.random.RandomState(21)
    engines = tuple(
        QueryEngine(STHCConfig(fidelity=f, use_pallas=True,
                               osave_chunk_windows=2, **cfg))
        for f in (fid.ideal(), fid.physical())
    )
    gratings = [
        engines[t % 2].record(
            jnp.asarray(rng.randn(9, 1, 4, 6, 3).astype(np.float32)), SIGNAL
        )
        for t in range(n)
    ]
    engines[0].set_resident(gratings)
    return engines[0], engines[1], gratings


@pytest.mark.parametrize("tenants", [4, 32])
def test_uniform_kernel_count_packs_at_its_own_stride(tenants):
    """A pool group whose members all have 9 kernels packs them every 9
    rows: 4 tenants take 36 arena rows and 32 take 288, none of them
    zero."""
    pooled, _, gratings = _nine_kernel_engines(tenants)
    arena = pooled._resident_arena(gratings[0::2])
    pool = arena.pool
    assert pool.align == 9 and pool.n_out == 9
    assert pool.re.shape[0] == 9 * (tenants // 2)
    assert list(pool.o_start) == [9 * i for i in range(tenants // 2)]
    assert pool.block_o == 9
    planes = np.asarray(pool.re).reshape(pool.re.shape[0], -1)
    assert np.all(np.abs(planes).max(axis=1) > 0)  # no zero row


def test_mixed_kernel_counts_keep_the_block_grid():
    """Members of different kernel counts keep the grouped kernel's
    ``BLOCK_O`` grid: 3 and 5 kernels each take an 8-row slot."""
    rng = np.random.RandomState(6)
    eng = QueryEngine(STHCConfig(fidelity=fid.ideal(), use_pallas=True))
    gs = [eng.record(jnp.asarray(rng.randn(o, 1, 4, 6, 3)
                                 .astype(np.float32)), SIGNAL)
          for o in (3, 5, 3)]
    pool = eng._resident_arena(gs).pool
    assert pool.align == pool.block_o == stmul_kernel.BLOCK_O == 8
    assert list(pool.o_start) == [0, 8, 16]
    assert pool.re.shape[0] == 24


@pytest.mark.parametrize(
    "stride, explicit, want",
    [(9, None, (9, 9)), (4, None, (4, 4)), (18, None, (18, 9)),
     (17, None, (17, 1)), (9, 4, (4, 4))],
)
def test_pool_stride_follows_the_members_kernel_count(stride, explicit, want):
    """The stride is the members' kernel count and the grouped kernel's
    O block the count itself, or above ``MAX_BLOCK_O`` rows its largest
    divisor that fits (18 is two blocks of 9, 17 seventeen of 1); an
    explicit ``stmul_block_o`` sets both, and the dense path packs with
    no alignment and no block."""
    rng = np.random.RandomState(stride)
    cfg = {} if explicit is None else {"stmul_block_o": explicit}
    eng = QueryEngine(STHCConfig(fidelity=fid.ideal(), use_pallas=True,
                                 **cfg))
    dense = QueryEngine(STHCConfig(fidelity=fid.ideal()))
    k = jnp.asarray(rng.randn(stride, 1, 4, 6, 3).astype(np.float32))
    gs = [eng.record(k, SIGNAL), eng.record(k + 1.0, SIGNAL)]
    assert eng._slot_layout(gs) == want
    assert dense._slot_layout(gs) == (1, None)
    align, block = want
    assert align % block == 0 and block <= stmul_kernel.MAX_BLOCK_O


def test_declaring_another_kernel_count_moves_the_group_to_the_grid():
    """The slot stride follows the pool group's declared members at
    serving time: declaring a 5-kernel tenant beside 9-kernel ones
    repacks the group's arena on the 8-row grid (16-row slots) and
    compiles the group's programs again for the new ``n_out`` and O
    block; declaring the 9-kernel tenants alone again packs them every 9
    rows.  Every answer equals the tenant's own ``query_stream``."""
    pooled, _, gratings = _nine_kernel_engines(4)
    nine = gratings[0::2]
    rng = np.random.RandomState(21)
    five = pooled.record(
        jnp.asarray(rng.randn(5, 1, 4, 6, 3).astype(np.float32)), SIGNAL
    )
    streams = _streams(2, seed=17)
    engines = [pooled] * 3
    members = nine + [five]

    def serve(batch, expect_align, expect_n_out):
        before = pooled.pool_stats()
        dets = pooled.query_stream_many(
            [(members[t], x) for t, x in batch], readout_k=1
        )
        after = pooled.pool_stats()
        (arena,) = pooled._arenas.values()
        assert arena.pool.align == arena.pool.block_o == expect_align
        assert arena.pool.n_out == expect_n_out
        _check(engines, members, batch, dets)
        return after["stream_traces"] - before["stream_traces"]

    # two rows each time, so only the stride can ask for a new program
    pooled.set_resident(nine)
    assert serve([(0, streams[0]), (1, streams[1])], 9, 9) == 1
    pooled.set_resident(members)
    assert serve([(0, streams[0]), (2, streams[1])],
                 stmul_kernel.BLOCK_O, 16) == 1
    assert pooled.pool_stats()["arena_builds"] == 2
    pooled.set_resident(nine)
    assert serve([(1, streams[0]), (0, streams[1])], 9, 9) == 0
    assert pooled.pool_stats()["arena_builds"] == 3


def test_span_layout_reads_whole_slots():
    """Four 9-kernel tenants sharing one stream read their 36-row union
    span; a lone request reads its own 9 rows."""
    starts, widths = [0, 9, 18, 27], [9] * 4
    shared = _span_layout(starts, widths, ["s"] * 4, 9, 36)
    assert shared.n_out == 36 and shared.row_of == [0]
    assert shared.o_off == starts
    lone = _span_layout([18], [9], ["s"], 9, 36)
    assert lone.n_out == 9 and lone.row_of == [18] and lone.o_off == [0]


def test_kernel_row_counters_read_each_rows_kernels():
    """Per physical row, the kernels its requests asked for against the
    rows it computed: 36/36 for four tenants on one stream, 9/9 for a
    lone request, and a tenant asked twice of one stream counts once."""
    pooled, _, gratings = _nine_kernel_engines(8)
    ideal = gratings[0::2]
    streams = _streams(2)

    def delta(reqs):
        before = pooled.pool_stats()
        pooled.query_stream_many(reqs, readout_k=1)
        after = pooled.pool_stats()
        return tuple(after[k] - before[k] for k in (
            "kernel_rows_requested", "kernel_rows_dispatched",
            "rows_dispatched"))

    assert delta([(g, streams[0]) for g in ideal]) == (36, 36, 1)
    assert delta([(ideal[2], streams[1])]) == (9, 9, 1)
    assert delta([(ideal[1], streams[0]), (ideal[1], streams[0])]) == (
        9, 9, 1)
    # two streams: each row computes the widest span, 18 rows
    assert delta([(ideal[0], streams[0]), (ideal[1], streams[0]),
                  (ideal[3], streams[1])]) == (27, 36, 2)


@pytest.mark.parametrize("shared", [True, False])
def test_nine_kernel_tenants_match_each_request_alone(shared):
    """Pooled top-K at 9-kernel tenants of both fidelities, on one shared
    stream or distinct streams, one-shot and through the chunked cursor:
    every request's top-1 equals its own ``query_stream``."""
    pooled, phys, gratings = _nine_kernel_engines(8)
    engines = [pooled if t % 2 == 0 else phys for t in range(8)]
    streams = _streams(8, seed=13)
    batch = [(t, streams[0] if shared else streams[t]) for t in range(8)]
    reqs = [(gratings[t], x) for t, x in batch]
    one = pooled.query_stream_many(reqs, readout_k=2)
    _check(engines, gratings, batch, one)
    chunked = pooled.query_stream_many(reqs, readout_k=2,
                                       max_buffer_windows=1)
    for a, b in zip(one, chunked):
        assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
        assert np.array_equal(np.asarray(a.index), np.asarray(b.index))
    assert all(a.pool.align == 9 for a in pooled._arenas.values())
    stats = pooled.pool_stats()
    assert stats["kernel_rows_requested"] == stats["kernel_rows_dispatched"]


def _kernel_fill_reader():
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "bench" / "metrics" / "search.kernel_fill.py")
    spec = importlib.util.spec_from_file_location("kernel_fill", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_kernel_fill_reader_reads_the_window_counters():
    """The benchmark's ``search.kernel_fill`` reads 100 % over a window of
    9-kernel tenants, each on its own stream, where slots hold no zero
    rows; 9 of 16 rows on the 8-row grid; and nothing from a program
    without the counters."""
    read = _kernel_fill_reader()
    pooled, _, gratings = _nine_kernel_engines(4)
    streams = _streams(2)
    before = pooled.pool_stats()
    pooled.query_stream_many(
        [(gratings[0], streams[0]), (gratings[2], streams[1])], readout_k=1
    )
    after = pooled.pool_stats()
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(pool_window=(before, after)))
    assert read(ctx) == 100.0
    grid = dict(after, kernel_rows_dispatched=before[
        "kernel_rows_dispatched"] + 2 * 16)
    ctx.cell.pool_window = (before, grid)
    assert read(ctx) == 100.0 * 18 / 32
    old = {k: v for k, v in after.items() if not k.startswith("kernel_")}
    ctx.cell.pool_window = (before, old)
    assert read(ctx) is None
    ctx.cell.pool_window = (None, None)
    assert read(ctx) is None
