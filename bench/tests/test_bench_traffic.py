"""The traffic generator and the rendered data: the same seed gives the
same work, every seed the same amount of it."""

from __future__ import annotations

import numpy as np
import pytest

from bench import render, traffic

BIG = 2**31 + 12345  # seeds reach past 32 signed bits


@pytest.mark.parametrize("name", ["fanout-closed4", "clips32-closed1"])
def test_schedule_is_deterministic_per_seed(name):
    mix = traffic.load(name)
    n = 1 if "clips_per_request" in mix else 8
    a, b = traffic.Schedule(mix, BIG, n), traffic.Schedule(mix, BIG, n)
    c = traffic.Schedule(mix, BIG + 1, n)
    units = [a.unit(i) for i in range(40)]
    assert units == [b.unit(i) for i in range(40)]
    assert units != [c.unit(i) for i in range(40)]


def test_units_share_or_split_streams_as_their_mix_says():
    fan = traffic.Schedule(traffic.load("fanout-closed4"), 3, 8)
    pool = traffic.load("fanout-closed4")["pool"]
    dist = traffic.Schedule({"outstanding": 4, "share": 1, "pool": pool}, 3, 8)
    for i in range(3 * pool // 8):
        assert len({s for _, s in fan.unit(i)}) == 1
        assert [t for t, _ in fan.unit(i)] == list(range(8))
        assert len({s for _, s in dist.unit(i)}) == 8
    used = [s for i in range(pool // 8) for _, s in dist.unit(i)]
    assert sorted(used) == list(range(pool))  # each stream once per cycle


def test_share_splits_a_unit_into_runs_of_tenants():
    mix = {"outstanding": 1, "share": 3, "pool": 9}
    s = traffic.Schedule(mix, 11, 8)  # runs of 3, 3 and 2 tenants
    for i in range(9):
        u = s.unit(i)
        streams = [e for _, e in u]
        assert [t for t, _ in u] == list(range(8))
        assert streams[:3] == [streams[0]] * 3 and streams[3:6] == [streams[3]] * 3
        assert streams[6:] == [streams[6]] * 2
        assert len({streams[0], streams[3], streams[6]}) == 3
    with pytest.raises(ValueError):
        traffic.Schedule(dict(mix, pool=10), 11, 8)


def test_mix_settings_lie_over_the_configuration():
    cfg = {"server": {"chunk_windows": 4, "use_pallas": True}}
    mix = {"server": {"chunk_windows": 1, "max_buffer_windows": 16}}
    assert traffic.settings(cfg, mix, "server") == {
        "chunk_windows": 1, "use_pallas": True, "max_buffer_windows": 16}
    assert traffic.settings(cfg, {}, "scheduler") == {}


def test_stream_pool_is_deterministic_distinct_and_8_bit():
    a = render.stream_pool(traffic.rng(BIG, 3), 6, (12, 16), 20)
    b = render.stream_pool(traffic.rng(BIG, 3), 6, (12, 16), 20)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert len({x.tobytes() for x in a}) == 6
    for x in a:
        assert x.shape == (1, 1, 12, 16, 20) and x.dtype == np.float32
        assert x.max() == 1.0
        np.testing.assert_array_equal(np.round(x * 255.0), x * 255.0)


@pytest.mark.parametrize("label", range(4))
def test_render_matches_the_programs_generator(label):
    from repro.data import kth_synthetic as kth

    want = kth.render_clip(label, 17, 2, kth.VideoSpec(20, 24, 12))
    got = render.render_clip(label, 17, 2, 20, 24, 12)
    np.testing.assert_array_equal(got, want)
