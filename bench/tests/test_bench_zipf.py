"""The Zipf-skewed many-tenant cell at a small size on the CPU: its
schedule, a whole run against the plain reference, and a planted fault
in the runtime composition that the comparison must catch."""

from __future__ import annotations

import collections
import copy
import json
import time

import numpy as np
import pytest

from bench import harness, traffic
from bench.drivers import video_search_zipf

WORKLOAD = "search-zipf-mix"


def tiny_cfg() -> dict:
    """The cell's configuration with every size cut: 12 tenants of both
    fidelities (alternating by rank), units of 4 requests."""
    with open(harness.ROOT / "bench/configs/kth-search-64t.json") as f:
        cfg = json.load(f)
    cfg.update(
        frame_hw=[12, 16], stream_frames=24, kernel_shape=[4, 6, 3],
        kernels_per_tenant=3,
        tenant_fidelity=["ideal" if t % 2 == 0 else "physical"
                         for t in range(12)],
        check_requests=12,
    )
    cfg["server"].update(window_frames=8, chunk_windows=2, cache_entries=12)
    cfg["scheduler"].update(max_batch=4)
    return cfg


def tiny_mix() -> dict:
    mix = copy.deepcopy(traffic.load("zipf-closed4"))
    mix.update(pool=12, unit_requests=4)
    return mix


def run(seconds: float = 2.0, trace: bool = False, prepare=None) -> dict:
    return harness.run_cell(
        WORKLOAD, 2**31 + 29, seconds, trace, time.perf_counter(),
        require_tpu=False, cfg=tiny_cfg(), mix=tiny_mix(), prepare=prepare,
    )


def test_schedule_is_deterministic_and_never_repeats_a_stream():
    mix = traffic.load("zipf-closed4")
    a = video_search_zipf.ZipfSchedule(mix, 2**33 + 5, 64)
    b = video_search_zipf.ZipfSchedule(mix, 2**33 + 5, 64)
    c = video_search_zipf.ZipfSchedule(mix, 2**33 + 6, 64)
    units = [a.unit(i) for i in range(200)]
    assert units == [b.unit(i) for i in range(200)]
    assert units != [c.unit(i) for i in range(200)]
    for u in units:
        assert len(u) == mix["unit_requests"]
        streams = [s for _, s in u]
        assert len(set(streams)) == len(streams)
        assert all(0 <= s < mix["pool"] for s in streams)
    # the compositions are nearly all new: what the cell exists to show
    comps = {tuple(sorted(t for t, _ in u)) for u in units}
    assert len(comps) > 0.9 * len(units)


def test_tenant_counts_follow_zipf():
    mix = traffic.load("zipf-closed4")
    sched = video_search_zipf.ZipfSchedule(mix, 7, 64)
    counts = collections.Counter(
        t for i in range(4000) for t, _ in sched.unit(i)
    )
    n = sum(counts.values())
    share = np.array([counts[t] / n for t in range(64)])
    rank = np.arange(1, 65, dtype=np.float64)
    want = rank ** -1.1 / np.sum(rank ** -1.1)
    # 32,000 draws: a share's standard error is at most 0.25 %
    assert np.max(np.abs(share - want)) < 0.01
    assert share[0] == pytest.approx(0.25, abs=0.01)
    assert share[:8].sum() == pytest.approx(0.63, abs=0.015)
    assert share[32:].sum() == pytest.approx(0.12, abs=0.01)


def test_run_is_correct_with_no_trace_in_the_window(capsys):
    r = run()
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"search_frames_per_s", "setup_s"}
    err = capsys.readouterr().err
    assert "pooled stream traces in window: 0, arena builds: 0" in err
    assert "compiles in window: backend=0 traces=0" in err
    assert "not exactly one group: 0" in err


def test_traced_run_reports_row_fill():
    r = run(trace=True)
    assert r["correct"], r["compared"]
    fill = r["metrics"]["search.row_fill"]["value"]
    assert 0 < fill <= 100


def _offset_to_another_tenant(cell):
    """In every pooled dispatch, the first row reads the next tenant's
    arena slot instead of its own."""
    eng = cell.server.sthc.engine
    inner = eng._stream_many_topk_fn

    def wrong(xs, pool_re, pool_im, rows, *args, n_out, **kw):
        rows = np.array(rows)
        rows[0] = (rows[0] + n_out) % pool_re.shape[0]
        return inner(xs, pool_re, pool_im, rows, *args, n_out=n_out, **kw)

    eng._stream_many_topk_fn = wrong


def test_wrong_runtime_offset_is_not_correct():
    broken = run(seconds=3.0, prepare=_offset_to_another_tenant)
    assert not broken["correct"], broken["compared"]
    assert broken["compared"]["score_err"]["value"] < float("inf")
