#!/usr/bin/env python3
"""Chip smoke test: the served video-search path and the paper's hybrid
classifier on a TPU, at the paper's geometry, through the normal entry
points.

    python chip_smoke.py             # one chip: phases 1 and 2
    python chip_smoke.py --chips 4   # mesh-sharded serving vs one device

Phase 1 serves 16 requests of 256-frame 60×80 streams to 8 tenants (9
kernels of 30×40×8 each, four ideal and four physical fidelity) through
``MicrobatchScheduler`` → ``VideoSearchServer`` with the Pallas kernels
on.  Scores are checked against a float64 correlation computed with
numpy on the host (ideal tenants) and against the same requests served
without Pallas (every tenant); the scheduler must never leave the pooled
rung, and the served dispatch must hold compiled Mosaic kernels.

Phase 2 classifies 32 clips of 60×80×16 with ``HybridClassifierServer``
and checks the logits against ``hybrid.forward``'s digital reference.

``--chips 4`` serves the phase-1 workload on a (data=1, model=4) mesh
and on one device, and compares the scores; it runs nothing else.

Runs everything in this one process.  Exits non-zero, without the
result line, when JAX finds no TPU or any check fails.  Wall times are
printed for information; they are not benchmarks.  Weights and data are
made from ``--seed``.  The last line of standard output is the JSON
result ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Tolerances.  Scores are compared relative to the scale of what they
# are maxima of, so a near-zero peak cannot blow up the ratio.
# float32 FFT correlation on the chip vs float64 correlation on the host,
# relative to the tenant kernel's peak |correlation| over the stream:
SCORE_RTOL_REFERENCE = 1e-3
# one engine path vs another (Pallas vs dense MAC, sharded vs one
# device), relative to the request's largest |score|:
SCORE_RTOL_PATHS = 1e-4
# classifier logits vs the digital reference (float32 matmuls), relative
# to max(1, max |reference logit|); the served head runs its matmuls at
# the chip's default precision:
LOGIT_RTOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Workload:
    """Phase-1 geometry.  The defaults are the paper's served geometry
    (``configs/sthc_kth.py`` with ``VideoSearchConfig``'s defaults)."""

    frame_hw: tuple[int, int] = (60, 80)
    kernel: tuple[int, int, int] = (30, 40, 8)
    n_kernels: int = 9
    stream_frames: int = 256
    window_frames: int = 64
    chunk_windows: int = 4
    tenants: int = 8  # first half ideal, second half physical
    requests: int = 16
    streams: int = 6
    max_batch: int = 8

    def plan(self) -> list[tuple[int, int]]:
        """(tenant, stream) per request.  Each batch of ``max_batch``
        requests holds every tenant once; tenants 2j and 2j+1 search the
        same stream, so every batch has shared streams to dedup within
        each fidelity group."""
        return [
            (
                r % self.tenants,
                ((r % self.tenants) // 2 + 2 * (r // self.max_batch))
                % self.streams,
            )
            for r in range(self.requests)
        ]


class Checks:
    """Collects check outcomes; a failed check never stops the run early
    (one chip run reports every failure), but fails it at the end."""

    def __init__(self) -> None:
        self.failures: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"check {name}: {'ok' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failures.append(name)

    def phase(self, name: str, fn, *args) -> None:
        try:
            fn(*args)
        except Exception:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            self(f"{name} raised", False)


def _timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"time {label}: {time.perf_counter() - t0:.3f} s (informative)",
          flush=True)
    return out


# ---------------------------------------------------------------------------
# Phase 1: video search through the front door
# ---------------------------------------------------------------------------


def make_tenants(wl: Workload, seed: int):
    import numpy as np

    from repro.core import fidelity

    rng = np.random.RandomState(seed)
    shape = (wl.n_kernels, 1) + wl.kernel
    return [
        (
            f"tenant{t}",
            rng.randn(*shape).astype(np.float32),
            fidelity.ideal() if t < wl.tenants // 2 else fidelity.physical(),
        )
        for t in range(wl.tenants)
    ]


def make_streams(wl: Workload, seed: int):
    import jax.numpy as jnp

    from repro.data import kth_synthetic as kth

    spec = kth.VideoSpec(*wl.frame_hw, wl.stream_frames)
    return [
        jnp.asarray(
            kth.render_clip(s % 4, 17 + (seed + s) % 9, s % 4, spec)[None, None]
        )
        for s in range(wl.streams)
    ]


def build_server(wl: Workload, tenants, use_pallas: bool, mesh_shape=None):
    from repro.launch.serve import VideoSearchConfig, VideoSearchServer

    server = VideoSearchServer(
        frame_hw=wl.frame_hw,
        cfg=VideoSearchConfig(
            window_frames=wl.window_frames,
            chunk_windows=wl.chunk_windows,
            cache_entries=wl.tenants,
            use_pallas=use_pallas,
            mesh_shape=mesh_shape,
        ),
    )
    for name, kernels, fid in tenants:
        server.add_tenant(name, kernels, fidelity=fid)
    return server


def serve_round(sched, wl: Workload, tenants, streams) -> list[dict]:
    """Submit every request of the plan, then wait on every future."""
    futs = [
        sched.submit(tenants[t][0], streams[s], block=True)
        for t, s in wl.plan()
    ]
    return [f.result(timeout=900) for f in futs]


def serve(label: str, wl: Workload, server, tenants, streams, rounds: int):
    """``rounds`` rounds of the plan through one scheduler: the first
    compiles, the rest are steady.  Returns the last round's results and
    the scheduler's metrics."""
    from repro.launch.serve import MicrobatchScheduler

    # a wait long enough that each round forms full batches of
    # ``max_batch`` requests (submitting hashes every clip)
    with MicrobatchScheduler(
        server, max_queue=64, max_batch=wl.max_batch, batch_wait_s=0.5
    ) as sched:
        for r in range(rounds):
            kind = "compile included" if r == 0 else "steady"
            outs = _timed(
                f"{label} round {r} ({kind}, {wl.requests} requests, "
                "0.5 s batch wait)",
                lambda: serve_round(sched, wl, tenants, streams),
            )
        return outs, sched.metrics()


def check_served_cleanly(check: Checks, label: str, server, sched_m: dict):
    """The ladder never left the pooled rung and nothing was retried,
    failed or served sequentially."""
    srv_m = server.metrics()
    pooled_brk = sched_m["ladder"]["breakers"]["pooled"]
    check(f"{label} ladder stayed pooled",
          sched_m["mode"] == "pooled" and pooled_brk["failures"] == 0,
          f"mode={sched_m['mode']} pooled_failures={pooled_brk['failures']}")
    check(f"{label} no failed or retried requests",
          sched_m["failed"] == 0 and sched_m["retries"] == 0,
          f"failed={sched_m['failed']} retries={sched_m['retries']} "
          f"completed={sched_m['completed']}")
    check(f"{label} pooled dispatch only",
          srv_m["sequential_dispatches"] == 0
          and srv_m["pooled_dispatches"] > 0,
          f"pooled={srv_m['pooled_dispatches']} "
          f"sequential={srv_m['sequential_dispatches']}")
    check(f"{label} shared streams deduped",
          srv_m["dedup"]["rows_saved"] > 0,
          f"rows_saved={srv_m['dedup']['rows_saved']} of "
          f"{srv_m['dedup']['rows_offered']}")
    print(f"{label} scheduler: batches={sched_m['batches']} "
          f"mean_batch={sched_m['mean_batch_size']:.2f} "
          f"p50={sched_m['latency_p50_ms']:.1f} ms "
          f"p99={sched_m['latency_p99_ms']:.1f} ms (informative)", flush=True)


def compare_paths(check: Checks, label: str, a: list[dict], b: list[dict]):
    """Scores and peak frames of the same requests served two ways."""
    import numpy as np

    errs = [
        float(np.max(np.abs(x["scores"] - y["scores"])))
        / max(float(np.max(np.abs(y["scores"]))), 1e-30)
        for x, y in zip(a, b)
    ]
    bitwise = all(
        np.array_equal(x["scores"], y["scores"])
        and np.array_equal(x["peak_frame"], y["peak_frame"])
        for x, y in zip(a, b)
    )
    frames_equal = all(
        np.array_equal(x["peak_frame"], y["peak_frame"]) for x, y in zip(a, b)
    )
    print(f"{label}: max relative score error {max(errs):.3e} "
          f"(tolerance {SCORE_RTOL_PATHS:g}); bitwise equal: {bitwise}",
          flush=True)
    check(f"{label} scores", max(errs) <= SCORE_RTOL_PATHS)
    check(f"{label} peak frames", frames_equal)
    return bitwise


def reference_peaks(stream, kernels):
    """Valid correlation of the whole stream with each kernel, in float64
    on the host (numpy's FFT, nothing of JAX or the engine), reduced to
    per-kernel peak, peak frame, per-frame peak (O, T') and peak |value|."""
    import numpy as np

    x = np.asarray(stream, np.float64)[0, 0]  # (H, W, T)
    k = np.asarray(kernels, np.float64)[:, 0]  # (O, kh, kw, kt)
    s = tuple(n + m - 1 for n, m in zip(x.shape, k.shape[1:]))
    axes = (-3, -2, -1)
    spec = np.fft.rfftn(x, s, axes=axes) * np.conj(np.fft.rfftn(k, s, axes=axes))
    valid = tuple(slice(0, n - m + 1) for n, m in zip(x.shape, k.shape[1:]))
    vol = np.fft.irfftn(spec, s, axes=axes)[(slice(None),) + valid]
    flat = vol.reshape(len(vol), -1)
    return (
        flat.max(-1),
        flat.argmax(-1) % vol.shape[-1],
        vol.max(axis=(1, 2)),
        np.abs(flat).max(-1),
    )


def check_reference(check: Checks, wl: Workload, tenants, streams, outs):
    """Ideal tenants against the float64 valid correlation of the whole
    stream."""
    import numpy as np

    errs, frame_hits, checked = [], 0, 0
    frames_ok = True
    for (t, s), out in zip(wl.plan(), outs):
        name, kernels, fid = tenants[t]
        if fid.name != "ideal":
            continue
        peak, frame, per_frame, scale = reference_peaks(streams[s], kernels)
        got_s, got_f = out["scores"][0], out["peak_frame"][0]
        errs.append(float(np.max(np.abs(got_s - peak) / scale)))
        tol = SCORE_RTOL_REFERENCE * scale
        o = np.arange(len(got_f))
        # a different frame is accepted only where the reference peaks
        # there too, within tolerance (a near-tie)
        frames_ok &= bool(
            np.all((got_f == frame) | (per_frame[o, got_f] >= peak - tol))
        )
        frame_hits += int(np.sum(got_f == frame))
        checked += len(got_f)
    print(f"reference: max relative score error {max(errs):.3e} "
          f"(tolerance {SCORE_RTOL_REFERENCE:g}); peak frames equal "
          f"{frame_hits}/{checked}", flush=True)
    check("ideal scores vs float64 reference",
          max(errs) <= SCORE_RTOL_REFERENCE)
    check("ideal peak frames vs float64 reference", frames_ok)


def record_dispatches(engine) -> list:
    """Keep the arguments of the engine's last fused pooled dispatch, so
    the served program can be lowered again and inspected."""
    inner = engine._stream_many_topk_fn
    last: list = []

    def recording(*args, **kwargs):
        last[:] = [(inner, args, kwargs)]
        return inner(*args, **kwargs)

    engine._stream_many_topk_fn = recording
    return last


def check_kernels_compiled(check: Checks, last: list):
    fn, args, kwargs = last[0]
    text = fn.lower(*args, **kwargs).as_text()
    found = {
        k: k in text for k in ("_stmul_kernel_grouped", "_topk_readout_kernel")
    }
    check("served dispatch holds Mosaic kernels",
          "tpu_custom_call" in text and all(found.values()),
          f"tpu_custom_call x{text.count('tpu_custom_call')} {found}")


def phase_video(check: Checks, wl: Workload, seed: int):
    from repro.kernels.stmul import ops as stmul_ops

    check("Pallas kernels compiled, not interpreted",
          not stmul_ops._use_interpret())
    tenants = make_tenants(wl, seed)
    streams = make_streams(wl, seed)
    server = _timed("phase 1 record 8 tenants (Pallas server)",
                    lambda: build_server(wl, tenants, use_pallas=True))
    print(f"phase 1 grating cache: {server.metrics()['cache']['bytes']} "
          "bytes resident", flush=True)
    last = record_dispatches(server.sthc.engine)
    outs, sched_m = serve("phase 1 Pallas", wl, server, tenants, streams, 2)
    check_served_cleanly(check, "phase 1 Pallas", server, sched_m)
    check_kernels_compiled(check, last)
    dense = build_server(wl, tenants, use_pallas=False)
    outs_d, sched_d = serve("phase 1 dense", wl, dense, tenants, streams, 1)
    check_served_cleanly(check, "phase 1 dense", dense, sched_d)
    compare_paths(check, "Pallas vs dense MAC", outs, outs_d)
    check_reference(check, wl, tenants, streams, outs)


# ---------------------------------------------------------------------------
# Phase 2: the paper's classifier
# ---------------------------------------------------------------------------


def phase_classifier(check: Checks, cfg, seed: int, n_clips: int = 32):
    import jax
    import numpy as np

    from repro.core import fidelity, hybrid
    from repro.data import kth_synthetic as kth
    from repro.launch.serve import HybridClassifierServer

    params = hybrid.init_params(jax.random.PRNGKey(seed), cfg)
    spec = kth.VideoSpec(cfg.height, cfg.width, cfg.frames)
    clips = jax.numpy.asarray(np.stack([
        kth.render_clip(i % 4, 17 + (seed + i) % 9, (i // 4) % 4, spec)[None]
        for i in range(n_clips)
    ]))
    server = _timed("phase 2 record classifier",
                    lambda: HybridClassifierServer(
                        params, cfg, fidelity=fidelity.ideal()))
    _timed("phase 2 classify (compile included)",
           lambda: jax.block_until_ready(server.logits(clips)))
    logits = np.asarray(_timed(
        f"phase 2 classify {n_clips} clips (steady)",
        lambda: jax.block_until_ready(server.logits(clips))))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, x: hybrid.forward(p, x, cfg, impl="digital")
        )(params, clips))
    err = float(np.max(np.abs(logits - ref))) / max(1.0, float(np.max(np.abs(ref))))
    agree = int(np.sum(logits.argmax(-1) == ref.argmax(-1)))
    print(f"classifier: max relative logit error {err:.3e} (tolerance "
          f"{LOGIT_RTOL:g}); classes equal {agree}/{n_clips}", flush=True)
    check("classifier logits vs hybrid.forward", err <= LOGIT_RTOL)


# ---------------------------------------------------------------------------
# --chips 4: mesh-sharded serving vs one device
# ---------------------------------------------------------------------------


def phase_mesh(check: Checks, wl: Workload, seed: int, mesh_shape):
    tenants = make_tenants(wl, seed)
    streams = make_streams(wl, seed)
    sharded = build_server(wl, tenants, use_pallas=True, mesh_shape=mesh_shape)
    print(f"mesh: {sharded.metrics()['mesh']}", flush=True)
    outs_m, sched_m = serve("mesh sharded", wl, sharded, tenants, streams, 2)
    check_served_cleanly(check, "mesh sharded", sharded, sched_m)
    single = build_server(wl, tenants, use_pallas=True)
    outs_1, sched_1 = serve("mesh single-device", wl, single, tenants,
                            streams, 2)
    check_served_cleanly(check, "mesh single-device", single, sched_1)
    compare_paths(check, "sharded vs single-device", outs_m, outs_1)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded serving comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "refusing to run on another backend", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from importlib import metadata

    from repro.configs import sthc_kth
    from repro.launch.compile_cache import enable_compile_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} libtpu={libtpu}",
          flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    check = Checks()
    wl = Workload()
    if args.chips == 4:
        check.phase("mesh", phase_mesh, check, wl, args.seed, (1, 4))
    else:
        check.phase("phase 1", phase_video, check, wl, args.seed)
        check.phase("phase 2", phase_classifier, check, sthc_kth.config(),
                    args.seed)
    if check.failures:
        print(f"chip_smoke: {len(check.failures)} check(s) failed: "
              f"{check.failures}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
