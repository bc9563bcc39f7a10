"""Whole runs of every cell's mix at a small size on the CPU: correct
against the plain reference, and every search batch exactly one group."""

from __future__ import annotations

import pytest

from bench import harness
from bench.tests import _tiny


@pytest.mark.parametrize("workload", sorted(_tiny.MIXES))
def test_run_is_correct_with_its_metrics(workload):
    spec = harness.load_spec()
    r = _tiny.run(workload)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in spec["end_to_end"]
            if harness.applies(m, workload)}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("share", [4, 2, 1])
def test_every_batch_is_one_group(share, monkeypatch):
    mix = dict(_tiny.mix("search-fanout-saturate"), share=share)
    monkeypatch.setattr(_tiny, "mix", lambda workload: mix)
    c = _tiny.cell("search-fanout-saturate")
    c.run(1.0)
    c.close()
    g = c.group_check()
    assert g["batches"] > 0
    assert g["not_one_group"] == 0
    assert len(c.served_by()) == g["batches"]
    rows = {len({s for _, s in c.schedule.unit(u)}) for u in c.served_by()}
    assert rows == {4 // share}  # distinct streams per batch


def test_traced_run_reports_only_per_layer_metrics():
    r = _tiny.run("search-fanout-saturate", trace=True)
    assert r["correct"]
    spec = harness.load_spec()
    layer = {m["name"] for m in spec["per_layer"]}
    assert set(r["metrics"]) <= layer
    assert "sched.dispatch_gap_ms" in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
