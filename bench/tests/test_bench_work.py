"""Work counts at the paper's geometry and the peak table."""

from __future__ import annotations

import json

import pytest

from bench import harness, peaks, work


def _search_geometry():
    with open(harness.ROOT / "bench/configs/kth-search-8t.json") as f:
        cfg = json.load(f)
    return cfg, work.StreamGeometry(
        frame_hw=tuple(cfg["frame_hw"]), frames=cfg["stream_frames"],
        kernel=tuple(cfg["kernel_shape"]), window_frames=cfg["server"]["window_frames"],
        chunk_windows=cfg["server"]["chunk_windows"], channels=cfg["channels"],
    )


def test_search_request_is_54_69_gflop():
    cfg, g = _search_geometry()
    flops = work.direct_correlation_flops(
        g.frame_hw, g.frames, g.kernel, cfg["kernels_per_tenant"]
    )
    assert flops == 31 * 41 * 249 * 9 * 9600 * 2 == 54_687_571_200


def test_classified_clip_is_1_977_gflop_plus_the_head():
    with open(harness.ROOT / "bench/configs/kth-classify.json") as f:
        cfg = json.load(f)
    assert work.pooled_features(cfg) == 3 * 5 * 3 * 9
    conv = 31 * 41 * 9 * 9 * 9600 * 2
    assert conv == 1_976_659_200
    assert work.classifier_flops(cfg) == conv + 2 * 405 * 128 + 2 * 128 * 4


def test_window_plan_at_paper_geometry():
    _, g = _search_geometry()
    assert g.valid == (31, 41, 249)
    assert (g.step, g.n_windows, g.n_launches) == (57, 5, 2)
    assert g.bins == 90 * 120 * 37


def test_mac_and_readout_work_from_shapes():
    _, g = _search_geometry()
    f = 90 * 120 * 37
    fan = work.spectral_mac_work(g, [36])  # one stream row, 4 tenants × 9
    assert fan.flops == 6 * 36 * 5 * f
    assert fan.bytes == 8 * f * (1 * 5 + 36 * 2 + 36 * 5)
    dist = work.spectral_mac_work(g, [9, 9, 9, 9])  # four rows of 9
    assert dist.flops == fan.flops
    assert dist.bytes == 8 * f * (4 * 5 + 36 * 2 + 36 * 5)
    rd = work.topk_readout_work(g, [36])
    pos = 31 * 41 * 249
    assert rd.flops == 2 * 36 * pos
    assert rd.bytes == 4 * (36 * pos + pos) + 2 * 4 * 36 * 2


def test_roofline_share_takes_the_binding_bound():
    p = peaks.peaks_for("TPU v5 lite")
    w = work.Work(flops=197e12 * 1e-3, bytes=819e9 * 2e-3)  # memory-bound
    assert work.percent_of_roofline(w, 4e-3, p) == pytest.approx(50.0)
    assert work.percent_of_roofline(w, 0.0, p) is None


def test_peak_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("n", [1, 2, 7, 71, 89, 119, 263, 1000])
def test_fast_lengths_match_the_programs(n):
    from repro.core import spectral_conv

    assert work.next_fast_len(n) == spectral_conv.next_fast_len(n)
