"""Small configurations and mixes for the benchmark's CPU tests: the
cells' own files with every size cut so that a run takes seconds with
the Pallas kernels in interpret mode."""

from __future__ import annotations

import copy
import json
import time

from bench import harness, traffic

MIXES = {
    "search-fanout-saturate": "fanout-closed4",
    "classify-b32": "clips32-closed1",
}


def search_cfg() -> dict:
    with open(harness.ROOT / "bench/configs/kth-search-8t.json") as f:
        cfg = json.load(f)
    cfg.update(
        frame_hw=[12, 16], stream_frames=24, kernel_shape=[4, 6, 3],
        kernels_per_tenant=3,
        tenant_fidelity=["ideal", "ideal", "physical", "physical"],
        check_requests=4,
    )
    cfg["server"].update(window_frames=8, chunk_windows=2, cache_entries=4)
    cfg["scheduler"].update(max_batch=4)
    return cfg


def classify_cfg() -> dict:
    with open(harness.ROOT / "bench/configs/kth-classify.json") as f:
        cfg = json.load(f)
    cfg.update(
        height=12, width=16, frames=6, k_h=4, k_w=6, k_t=3,
        pool_window=[2, 2, 2], hidden=8, num_kernels=3, check_requests=2,
    )
    return cfg


def mix(workload: str) -> dict:
    m = copy.deepcopy(traffic.load(MIXES[workload]))
    if "clips_per_request" in m:
        m.update(clips_per_request=4, pool=3)
    else:
        m.update(pool=8)
    return m


def cfg(workload: str) -> dict:
    return classify_cfg() if workload.startswith("classify") else search_cfg()


def run(workload: str, seed: int = 2**31 + 17, seconds: float = 1.0,
        trace: bool = False, prepare=None) -> dict:
    """One whole run on the CPU, the device check skipped."""
    return harness.run_cell(
        workload, seed, seconds, trace, time.perf_counter(),
        require_tpu=False, cfg=cfg(workload), mix=mix(workload),
        prepare=prepare,
    )


def cell(workload: str, seed: int = 5, config: dict | None = None):
    """A set-up cell of the small size (or of ``config``), for tests that
    drive it."""
    config = config if config is not None else cfg(workload)
    c = harness.driver_for(config).Cell(
        config, mix(workload), seed, log=lambda *a: None
    )
    c.setup()
    return c
