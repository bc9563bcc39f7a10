"""Trace reduction: busy and idle time, kernel time, idle gaps named by
the harness's host spans — on a hand-built trace whose every number is
read off below.  Kernel operations carry the names a TPU compile gives
them: the custom call is named after the function that makes the
``pallas_call`` (``vmap_jit_<name>__.<n>`` under ``vmap``), and the pads
that function adds carry its name only in their op name."""

from __future__ import annotations

import pytest

from bench import tracing

# Device: fusion [1000, 3000) ns; the top-k readout's custom call
# [2000, 5000) (overlaps it); the grouped MAC's custom call [7000, 8000)
# with a pad of the same function [7000, 7500) under it; fusion
# [12000, 13000) (past the window).  Window [500, 10500).  Host spans:
# submit [3000, 6500), search_batch [6500, 9000).
SYNTHETIC = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events {
      metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000
      stats { metadata_id: 1 str_value: "%custom-call.7 = (f32[8,16,1]) custom-call(%pad.2), custom_call_target=\\"tpu_custom_call\\", metadata={op_name=\\"jit(step)/jit(topk_readout_pallas)/pallas_call\\"}" }
    }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events {
      metadata_id: 5 offset_ps: 6000000 duration_ps: 500000
      stats { metadata_id: 1 str_value: "%pad.4 = f32[71,1,4096] pad(%x), metadata={op_name=\\"jit(step)/vmap(jit(spectral_mac_grouped_pallas))/jit(_pad)/pad\\"}" }
    }
    events { metadata_id: 1 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.7" } }
  event_metadata { key: 3 value { id: 3 name: "vmap_jit_spectral_mac_grouped_pallas__.1" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
  event_metadata { key: 5 value { id: 5 name: "pad.4" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 3
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 3500000 }
    events { metadata_id: 3 offset_ps: 6500000 duration_ps: 2500000 }
    events { metadata_id: 4 offset_ps: 100000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.search_batch" } }
  event_metadata { key: 4 value { id: 4 name: "not_ours" } }
}
"""

LABELS = (("bench.search_batch", "search_batch host part"),
          ("bench.submit", "submit"))


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return tracing.Trace(ProfileData.from_text_proto(SYNTHETIC))


def test_window_and_busy(synthetic):
    assert synthetic.window == (500, 10500)
    assert synthetic.window_s == pytest.approx(10e-6)
    # union of [1000, 5000) and [7000, 8000); the op at 12000 is outside
    assert synthetic.busy_intervals("/device:TPU:0") == [(1000, 5000),
                                                         (7000, 8000)]
    assert synthetic.busy_s() == pytest.approx(5e-6)


def test_kernel_time_and_top_ops(synthetic):
    # the custom call only, not the pad its function makes
    assert synthetic.kernel_s("spectral_mac_grouped_pallas") == pytest.approx(1e-6)
    # named by its HLO text, not its event name
    assert synthetic.kernel_s("topk_readout_pallas") == pytest.approx(3e-6)
    assert synthetic.kernel_s("no_such_kernel") == 0.0
    # only ops that reach into the window count
    assert synthetic.top_ops(2) == [["custom-call.7", pytest.approx(3e-6)],
                                    ["fusion.1", pytest.approx(2e-6)]]


def test_idle_gaps_named_by_host_spans(synthetic):
    gaps = synthetic.idle_gaps(LABELS, "between calls")
    assert gaps == [("between calls", 500, 1000), ("submit", 5000, 7000),
                    ("search_batch host part", 8000, 10500)]
    assert synthetic.host == [e for e in synthetic.host
                              if e.name.startswith("bench.")]


def test_busy_inside_host_spans(synthetic):
    spans = synthetic.spans("bench.search_batch")
    assert spans == [(6500, 9000)]
    assert synthetic.overlap_busy_s(spans) == pytest.approx(1e-6)
