"""Named stages of the served path (``repro.core.spans``): every host
span appears in a profiler trace of one scheduled search batch and one
classifier call, nested as the stages nest, and the pooled driver's
lowered program names its device stages in its ops' ``op_name``."""

from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fidelity, hybrid
from repro.launch.serve import (
    HybridClassifierServer,
    MicrobatchScheduler,
    VideoSearchConfig,
    VideoSearchServer,
)

SEARCH_SPANS = (
    "sthc.sched.hash", "sthc.sched.cycle", "sthc.search.batch",
    "sthc.search.group", "sthc.search.gratings", "sthc.engine.layout",
    "sthc.engine.dispatch", "sthc.search.wait", "sthc.search.results",
)
CLASSIFY_SPANS = ("sthc.classify", "sthc.classify.conv", "sthc.classify.head")
DEVICE_SCOPES = ("sthc.encode", "sthc.rfft", "sthc.mac", "sthc.irfft",
                 "sthc.readout")
FRAME_HW = (12, 16)


def _server() -> VideoSearchServer:
    """Two tenants on one stream, one at each fidelity, through the
    cell's path: pooled dispatch, Pallas MAC, fused top-1, dedup."""
    cfg = VideoSearchConfig(window_frames=8, chunk_windows=2, use_pallas=True)
    server = VideoSearchServer(frame_hw=FRAME_HW, cfg=cfg)
    rng = np.random.RandomState(0)
    for name, fid in (("ideal", fidelity.ideal()),
                      ("physical", fidelity.physical())):
        kernels = rng.randn(3, 1, 4, 6, 3).astype(np.float32)
        server.add_tenant(name, kernels, fidelity=fid)
    return server


def _stream() -> np.ndarray:
    rng = np.random.RandomState(1)
    return rng.rand(1, 1, *FRAME_HW, 24).astype(np.float32)


def _host_spans(trace_dir: str) -> list[tuple[int, str, int, int]]:
    """(thread line, name, start ns, end ns) of every ``sthc.`` host
    event in the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            out += [(li, e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events if e.name.startswith("sthc.")]
    return out


def _inside(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host spans of one microbatch (warmed first, so the trace holds a
    steady batch) and one classifier call."""
    server = _server()
    stream = _stream()
    hcfg = hybrid.HybridConfig(
        height=12, width=16, frames=6, num_kernels=2, k_h=4, k_w=6, k_t=3,
        pool_window=(2, 2, 2), hidden=8,
    )
    clf = HybridClassifierServer(
        hybrid.init_params(jax.random.PRNGKey(0), hcfg), hcfg
    )
    clips = jnp.asarray(np.random.RandomState(2).rand(2, 1, 12, 16, 6),
                        jnp.float32)
    out = tmp_path_factory.mktemp("trace")
    sched = MicrobatchScheduler(server, max_batch=2, batch_wait_s=1.0)

    def one_batch():
        futs = [sched.submit(t, stream, block=True)
                for t in ("ideal", "physical")]
        return [f.result(timeout=300) for f in futs]

    try:
        one_batch()
        clf.logits(clips).block_until_ready()
        with jax.profiler.trace(str(out)):
            answers = one_batch()
            # joins the batcher: its cycle span has ended before the trace
            sched.close()
            clf.logits(clips).block_until_ready()
    finally:
        sched.close()
    assert all("scores" in a for a in answers)
    return _host_spans(str(out))


def test_every_host_span_is_traced(traced):
    names = {name for _, name, _, _ in traced}
    missing = set(SEARCH_SPANS + CLASSIFY_SPANS) - names
    assert not missing, f"spans not in the trace: {sorted(missing)}"
    # one hash per request, one scheduler cycle for the batch
    assert sum(1 for s in traced if s[1] == "sthc.sched.hash") == 2
    assert sum(1 for s in traced if s[1] == "sthc.sched.cycle") == 1


def test_host_spans_nest_in_one_thread(traced):
    by = {}
    for s in traced:
        by.setdefault(s[1], []).append(s)
    (cycle,) = by["sthc.sched.cycle"]
    (batch,) = by["sthc.search.batch"]
    assert _inside(batch, cycle)
    for name in SEARCH_SPANS[3:]:
        assert all(_inside(s, batch) for s in by[name]), name
    # the hashes run in the submitter's thread, not the batcher's
    assert all(s[0] != cycle[0] for s in by["sthc.sched.hash"])
    (call,) = by["sthc.classify"]
    for name in ("sthc.classify.conv", "sthc.classify.head"):
        (s,) = by[name]
        assert _inside(s, call), name


def test_pooled_driver_names_its_device_stages():
    """The lowered pooled driver (one program per pool group) carries
    every device scope in its ops' ``op_name`` metadata."""
    server = _server()
    engine = server.sthc.engine
    calls = []
    inner = engine._stream_many_topk_fn

    def record(*args, **kw):
        calls.append((args, kw))
        return inner(*args, **kw)

    engine._stream_many_topk_fn = record
    server.search_batch([("ideal", _stream()), ("physical", _stream())])
    assert len(calls) == 2  # one dispatch per pool group
    names = set()
    for args, kw in calls:
        hlo = inner.lower(*args, **kw).as_text(dialect="hlo", debug_info=True)
        names |= set(re.findall(r'op_name="([^"]*)"', hlo))
    path = "\n".join(sorted(names))
    for scope in DEVICE_SCOPES:
        assert re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", path,
                         re.M), scope


def test_pooled_driver_scopes_hold_the_lane_layout(monkeypatch):
    """With the TPU's DFT transforms, the pooled driver reads the MAC's
    lane-plane output as it lies: the grouped kernel sits under
    ``sthc.mac`` and the inverse transform's lane-plane contractions
    under ``sthc.irfft``, with no slice of the kernel's output between
    them."""
    from repro.core import spectral_conv

    monkeypatch.setattr(spectral_conv, "_use_dft", lambda: True)
    server = _server()
    engine = server.sthc.engine
    calls = []
    inner = engine._stream_many_topk_fn

    def record(*args, **kw):
        calls.append((args, kw))
        return inner(*args, **kw)

    engine._stream_many_topk_fn = record
    server.search_batch([("ideal", _stream()), ("physical", _stream())])
    assert len(calls) == 2
    assert all(args[1].ndim == 4 for args, _ in calls)  # lane planes
    assert engine.pool_stats()["native_layout_dispatches"] == 2
    args, kw = calls[0]
    hlo = inner.lower(*args, **kw).as_text(dialect="hlo", debug_info=True)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    mac = [n for n in names if "spectral_mac_grouped_pallas" in n]
    assert mac and all(re.search(r"(^|[/(])sthc\.mac[/)]", n) for n in mac)
    assert not any("sthc.mac" in n and n.endswith("/slice") for n in names)
    lanes = [n for n in names if "...khw,ha->...kaw" in n]
    assert lanes and all(re.search(r"(^|[/(])sthc\.irfft[/)]", n)
                         for n in lanes)


def test_classifier_conv_program_names_its_device_stages():
    """The classifier's optical layer is one jitted program per call, and
    its lowered ops carry the one-shot query's device scopes."""
    hcfg = hybrid.HybridConfig(
        height=12, width=16, frames=6, num_kernels=2, k_h=4, k_w=6, k_t=3,
        pool_window=(2, 2, 2), hidden=8,
    )
    clf = HybridClassifierServer(
        hybrid.init_params(jax.random.PRNGKey(0), hcfg), hcfg
    )
    engine = clf.sthc.engine
    calls = []
    inner = engine._query_one_fn

    def record(*args, **kw):
        calls.append((args, kw))
        return inner(*args, **kw)

    engine._query_one_fn = record
    clips = np.random.RandomState(2).rand(2, 1, 12, 16, 6).astype(np.float32)
    clf.logits(clips).block_until_ready()
    assert len(calls) == 1  # one dispatch for the whole conv
    (args, kw), = calls
    hlo = inner.lower(*args, **kw).as_text(dialect="hlo", debug_info=True)
    path = "\n".join(sorted(set(re.findall(r'op_name="([^"]*)"', hlo))))
    for scope in ("sthc.encode", "sthc.rfft", "sthc.mac", "sthc.irfft"):
        assert re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", path,
                         re.M), scope
