"""Streaming video event search — the STHC's native serving mode.

Reference event clips ("what to look for") are recorded once into the
grating; a long video stream is then pushed through the coherence-window
segmentation (overlap-save, paper Fig. 1C) and each reference produces a
correlation peak wherever its event occurs.

The server is multi-tenant *and mixed-fidelity*: every named reference
kernel set (tenant) registers with its own fidelity pipeline — the
ordered stack of physics stages from :mod:`repro.core.fidelity` — and
all of them share one grating cache with an LRU budget in entries and
bytes (each query routes to its tenant's grating, re-recorded
transparently if evicted; the cache key's pipeline fingerprint keeps
fidelities apart).  The demo registers the same action-class references
three times on ONE server: through the exact *ideal* correlator, the
full *physical* model, and a quantization-only stage subset; the stream
hides one 'running' clip among distractors all three must localize.

Detection is served by the **fused in-kernel readout**
(``VideoSearchConfig.fused_readout``, on by default): each coherence
window chunk's correlation scores collapse in-kernel to the K best
(score, position) pairs per reference, so the full correlation volume
never materializes — constant output-side memory at any stream length,
bitwise equal to the stitched volume's max/argmax.  Related knobs:
``readout_topk`` reports the K best detections per reference
(``topk_scores`` / ``topk_frames`` in the result), ``readout_block_o`` /
``readout_block_l`` tune the Pallas readout tiles on real hardware, and
``search(..., return_volume=True)`` opts one call back into the stitched
volume when the caller needs the raw correlation map.

The production front door is the **async microbatch scheduler**
(queue → batcher → pooled executor): callers submit requests and get
futures, the scheduler coalesces concurrent mixed-tenant requests into
microbatches, and same-geometry tenants are answered from one pooled
grating arena in a single device dispatch.  The demo pushes the same
stream through all three fidelities concurrently that way and prints
the scheduler's latency percentiles and batch counters.

Run:  PYTHONPATH=src python examples/serve_video.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import fidelity
from repro.data import kth_synthetic as kth
from repro.launch.serve import (
    MicrobatchScheduler,
    VideoSearchConfig,
    VideoSearchServer,
)

SPEC = kth.VideoSpec(height=24, width=32, frames=12)


def main() -> None:
    # reference events: one exemplar per action class (subject 20 — unseen)
    refs = np.stack(
        [kth.render_clip(label, 20, 0, SPEC) for label in range(4)]
    )[:, None]  # (4, 1, H, W, T)
    refs = refs - refs.mean(axis=(2, 3, 4), keepdims=True)  # zero-mean match
    refs = jnp.asarray(refs.astype(np.float32))

    # a long stream: waving ... running ... boxing (subject 21, unseen)
    segments = [kth.render_clip(1, 21, 1, SPEC), kth.render_clip(3, 21, 1, SPEC),
                kth.render_clip(2, 21, 1, SPEC)]
    stream = np.concatenate(segments, axis=-1)[None, None]  # (1,1,H,W,3T)
    stream = jnp.asarray(stream.astype(np.float32))

    # The references are recorded into the shared grating cache once, at
    # registration time; every subsequent search diffracts off the same
    # stored spectrum (record-once / stream-forever).  chunk_windows
    # batches the coherence windows through vmap'd FFTs instead of a
    # strictly sequential scan.  Fidelity is per *kernel set*: one
    # server, one cache, three pipelines — the cache key's pipeline
    # fingerprint keeps the gratings apart even though the kernel bytes
    # are identical.
    server = VideoSearchServer(
        frame_hw=(SPEC.height, SPEC.width),
        cfg=VideoSearchConfig(window_frames=24, chunk_windows=2),
    )
    server.add_kernel_set("actions", refs)  # server default: ideal()
    server.add_kernel_set("actions-physical", refs,
                          fidelity=fidelity.physical())
    server.add_kernel_set(
        "actions-slm-only", refs,
        fidelity=fidelity.pipeline(fidelity.SLMQuantize(), name="slm-only"),
    )

    out = server.search(stream, tenant="actions")
    print(f"stream of {stream.shape[-1]} frames searched in "
          f"{out['windows']} coherence windows "
          f"({out['latency_s']*1000:.0f} ms)")
    names = kth.CLASSES
    scores = out["scores"][0]
    peaks = out["peak_frame"][0]
    for i, name in enumerate(names):
        print(f"  reference '{name:9s}': score {scores[i]:7.2f} "
              f"peak at frame {peaks[i]:3d}")
    # localization check: the 'running' reference must peak inside the
    # running segment (frames 12..23 of the stream)
    run_peak = int(peaks[3])
    ok = 12 - SPEC.frames // 2 <= run_peak <= 23
    print(f"'running' reference localizes the running segment "
          f"(frames 12-23): peak {run_peak} -> {'OK' if ok else 'MISS'}")

    # the scores above came from the fused readout (no correlation
    # volume was ever built); opting one call back into the stitched
    # volume shows they are bitwise the volume's max — and a top-3
    # server reports the runner-up detections per reference
    vol_out = server.search(stream, tenant="actions", return_volume=True)
    exact = bool(np.array_equal(out["scores"], vol_out["scores"]))
    print(f"fused readout == stitched volume max: {exact} "
          f"(a {'x'.join(str(d) for d in vol_out['volume'].shape)} "
          f"volume avoided per search; the gap grows with stream "
          f"length and references)")
    topk_server = VideoSearchServer(
        frame_hw=(SPEC.height, SPEC.width),
        cfg=VideoSearchConfig(
            window_frames=24, chunk_windows=2, readout_topk=3
        ),
    )
    topk_server.add_kernel_set("actions", refs)
    t3 = topk_server.search(stream, tenant="actions")
    frames3 = ", ".join(str(f) for f in t3["topk_frames"][0][3])
    print(f"top-3 'running' detections peak at frames [{frames3}]")

    # the same stream through all three fidelities *concurrently*, via
    # the async microbatch front end: submit returns futures, the
    # scheduler coalesces the requests into one microbatch, and the
    # pooled executor answers every same-geometry tenant from one
    # grating arena in a single device dispatch.
    with MicrobatchScheduler(
        server, max_queue=16, max_batch=8, batch_wait_s=0.01
    ) as sched:
        futs = {
            tenant: sched.submit(tenant, stream)
            for tenant in ("actions-physical", "actions-slm-only")
        }
        for tenant, fut in futs.items():
            tout = fut.result(timeout=120)
            fid_name = server.metrics()["tenants"][tenant]["fidelity"]
            print(
                f"[{fid_name:9s}] 'running' score "
                f"{tout['scores'][0][3]:7.2f} (ideal {scores[3]:7.2f}), "
                f"peak at frame {tout['peak_frame'][0][3]}, "
                f"end-to-end {tout['queue_latency_s'] * 1e3:.0f} ms"
            )
        sm = sched.metrics()
    print(
        f"scheduler: {sm['completed']} served in {sm['batches']} "
        f"microbatches (mean size {sm['mean_batch_size']:.1f}), "
        f"p50 {sm['latency_p50_ms']:.0f} ms / p99 "
        f"{sm['latency_p99_ms']:.0f} ms, {sm['rejected']} shed"
    )

    # serving metrics: cache behavior across fidelities
    m = server.metrics()
    c = m["cache"]
    print(f"cache: {c['hits']} hits / {c['misses']} misses / "
          f"{c['evictions']} evictions, {c['entries']} gratings "
          f"({c['bytes']/1e6:.2f} MB resident) — "
          f"{len(set(t['fidelity'] for t in m['tenants'].values()))} "
          f"fidelities on one server")


if __name__ == "__main__":
    main()
