"""Driver for configurations of kind ``video_search``: tenants' kernel
banks served through ``MicrobatchScheduler`` → ``VideoSearchServer``.

A unit of traffic is a group of one request per tenant, submitted back
to back by one thread.  With ``max_batch`` equal to the tenant count
and a batch wait longer than one group's submission, every batch the
scheduler forms is exactly one group; a proxy around ``search_batch``
records each batch and the harness checks that it was one.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import numpy as np

from bench import render, traffic, work
from bench.reference import control
from bench.reference.video_search import SearchReference

WAIT_PAST_CLOSE_S = 60.0


@dataclasses.dataclass
class Request:
    unit: int
    tenant: int
    stream: int
    sent: float  # perf_counter time
    done: float = float("nan")
    result: dict | None = None
    error: BaseException | None = None


@dataclasses.dataclass
class Batch:
    start: float
    end: float
    members: tuple  # sorted (tenant name, id of the clip array)


def program_pipeline(fid: dict):
    """The program's fidelity pipeline with the configuration's stated
    parameters."""
    from repro.core import fidelity as pf

    if fid["name"] == "ideal":
        return pf.ideal()
    pulse = fid["pulse"]
    return pf.FidelityPipeline(
        (
            pf.PseudoNegative(),
            pf.SLMQuantize(fid["slm_bits"]),
            pf.IHBEnvelope(),
            pf.T2Apodize(),
            pf.EchoGain(),
            pf.PulseCompensate(
                compensate=pulse["compensate"],
                duration_frames=pulse["duration_frames"],
                floor=pulse["floor"],
            ),
        ),
        name="physical",
    )


def device_models(cfg: dict):
    """SLM and atomic-medium models as the configuration states them."""
    from repro.core import atomic, optics

    phys = cfg["fidelities"].get("physical")
    if phys is None:
        return optics.SLMConfig(), atomic.AtomicConfig()
    if phys["storage_interval_s"] != 0.0:
        raise ValueError("the served path records at storage interval 0")
    slm = optics.SLMConfig(bits=phys["slm_bits"])
    atoms = atomic.AtomicConfig(
        t2_s=phys["t2_s"],
        frame_time_s=phys["frame_time_s"],
        ihb_profile=phys["ihb"]["profile"],
        coverage=phys["ihb"]["coverage"],
    )
    return slm, atoms


def make_kernels(seed: int, shape, bits: int):
    """Every tenant's kernel bank, made on the device in one call:
    normal draws brought to the SLM's signed levels with a range of
    their own per output kernel, as a bank trained with the SLM in the
    loop is stored."""
    import jax
    import jax.numpy as jnp

    levels = float(2**bits - 1)

    @jax.jit
    def make(key):
        k = jax.random.normal(key, shape, jnp.float32)
        rng_ = jnp.max(jnp.abs(k), axis=(-4, -3, -2, -1), keepdims=True)
        return jnp.round(k / rng_ * levels) * (rng_ / levels)

    key = jax.random.key(int(traffic.rng(seed, 5).integers(2**31)))
    return np.asarray(make(key))


class Cell:
    # what the host was doing in a device-idle gap, by harness span
    IDLE_LABELS = (
        ("bench.search_batch", "search_batch host part"),
        ("bench.submit", "submit and clip hashing"),
    )
    IDLE_FALLBACK = "batcher between search_batch calls"

    def __init__(self, cfg: dict, mix: dict, seed: int, log=print):
        self.cfg, self.mix, self.seed, self.log = cfg, mix, int(seed), log
        self.n_tenants = len(cfg["tenant_fidelity"])
        self.fids = [cfg["fidelities"][f] for f in cfg["tenant_fidelity"]]
        self.names = [f"tenant{t}" for t in range(self.n_tenants)]
        self.schedule = traffic.Schedule(mix, seed, self.n_tenants)
        self.server_settings = traffic.settings(cfg, mix, "server")
        self.geometry = work.StreamGeometry(
            frame_hw=tuple(cfg["frame_hw"]),
            frames=cfg["stream_frames"],
            kernel=tuple(cfg["kernel_shape"]),
            window_frames=self.server_settings["window_frames"],
            chunk_windows=self.server_settings["chunk_windows"],
            channels=cfg["channels"],
        )
        self.batches: list[Batch] = []
        self.requests: list[Request] = []
        self.submit_s: list[float] = []  # one thread sending one unit
        self.window: tuple[float, float] = (0.0, 0.0)

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from repro.launch.serve import (
            MicrobatchScheduler, VideoSearchConfig, VideoSearchServer,
        )

        cfg = self.cfg
        kh, kw, kt = cfg["kernel_shape"]
        shape = (self.n_tenants, cfg["kernels_per_tenant"], cfg["channels"],
                 kh, kw, kt)
        self.kernels = make_kernels(self.seed, shape, cfg["kernel_bits"])
        self.streams = render.stream_pool(
            traffic.rng(self.seed, 3), self.mix["pool"], cfg["frame_hw"],
            cfg["stream_frames"],
        )
        slm, atoms = device_models(cfg)
        self.server = VideoSearchServer(
            frame_hw=tuple(cfg["frame_hw"]),
            cfg=VideoSearchConfig(**self.server_settings, slm=slm, atoms=atoms),
        )
        for name, k, fid in zip(self.names, self.kernels, self.fids):
            self.server.add_tenant(name, k, fidelity=program_pipeline(fid))
        self._install_proxy()
        self.sched = MicrobatchScheduler(
            self.server, **traffic.settings(cfg, self.mix, "scheduler")
        )
        # warm up: the cell's own batch composition, twice (the second
        # call must find every program compiled)
        for i in range(2):
            for fut in self._submit_unit(i):
                fut.result(timeout=1200)
        self.batches.clear()
        self.requests.clear()

    def _install_proxy(self) -> None:
        import jax

        inner = self.server.search_batch
        batches = self.batches

        def search_batch(requests, **kw):
            start = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.search_batch"):
                out = inner(requests, **kw)
            batches.append(Batch(
                start, time.perf_counter(),
                tuple(sorted((t, id(c)) for t, c in requests)),
            ))
            return out

        self.server.search_batch = search_batch

    def _unit_members(self, i: int) -> tuple:
        return tuple(sorted(
            (self.names[t], id(self.streams[s]))
            for t, s in self.schedule.unit(i)
        ))

    def _submit_unit(self, i: int) -> list:
        import jax

        futs = []
        sent = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            for t, s in self.schedule.unit(i):
                req = Request(unit=i, tenant=t, stream=s, sent=sent)
                fut = self.sched.submit(self.names[t], self.streams[s],
                                        block=True)
                fut.add_done_callback(lambda f, r=req: self._finish(r, f))
                self.requests.append(req)
                futs.append(fut)
        self.submit_s.append(time.perf_counter() - sent)
        return futs

    @staticmethod
    def _finish(req: Request, fut) -> None:
        req.done = time.perf_counter()
        try:
            req.result = fut.result()
        except Exception as exc:  # noqa: BLE001 — counted as failed
            req.error = exc

    # -- the measured window -----------------------------------------------

    def run(self, seconds: float) -> None:
        t0 = time.perf_counter()
        end = t0 + seconds
        inflight: collections.deque = collections.deque()
        i = 0
        for _ in range(int(self.mix["outstanding"])):
            inflight.append(self._submit_unit(i))
            i += 1
        while inflight:
            for fut in inflight.popleft():
                fut.exception(timeout=seconds + WAIT_PAST_CLOSE_S)
            if time.perf_counter() < end:
                inflight.append(self._submit_unit(i))
                i += 1
        self.window = (t0, end)

    def close(self) -> None:
        self.sched.close()
        self.server = None

    # -- what the window measured -------------------------------------------

    def in_window(self) -> list[Request]:
        return [r for r in self.requests if r.done <= self.window[1]]

    def attempted_failed(self) -> tuple[int, int]:
        reqs = [r for r in self.requests if r.sent < self.window[1]]
        failed = sum(1 for r in reqs if r.result is None)
        return len(reqs), failed

    def end_to_end(self) -> dict:
        t0, end = self.window
        frames = sum(self.cfg["stream_frames"] for r in self.in_window() if r.result)
        return {"search_frames_per_s": frames / (end - t0)}

    def served_by(self) -> dict[int, Batch]:
        """The batch that served each unit.  Units and batches pair in
        order (the queue is FIFO); a unit whose requests were split over
        batches, or merged with another's, matches none."""
        out: dict[int, Batch] = {}
        j = 0
        for u in sorted({r.unit for r in self.requests}):
            members = self._unit_members(u)
            for k in range(j, min(j + 4, len(self.batches))):
                if self.batches[k].members == members:
                    out[u], j = self.batches[k], k + 1
                    break
        return out

    def group_check(self) -> dict:
        """Batches in the window that were not exactly one unit."""
        served = len(self.served_by())
        return {"batches": len(self.batches),
                "not_one_group": len(self.batches) - served}

    def dispatch_gaps_s(self) -> list[float]:
        return [b.start - a.end for a, b in zip(self.batches, self.batches[1:])]

    def dispatch_work(self) -> dict:
        """Useful work of every pooled dispatch in the window, by kernel:
        each batch makes one dispatch per pool group (tenants sharing
        encode semantics), with one row per distinct stream."""
        total = {"stmul_grouped": work.ZERO, "topk_readout": work.ZERO}
        n_o = self.cfg["kernels_per_tenant"]
        units = {r.unit for r in self.requests}
        for u in units:
            pools: dict[str, dict[int, int]] = {}
            for t, s in self.schedule.unit(u):
                rows = pools.setdefault(self.fids[t]["name"], {})
                rows[s] = rows.get(s, 0) + n_o
            for rows in pools.values():
                kpr = list(rows.values())
                total["stmul_grouped"] += work.spectral_mac_work(self.geometry, kpr)
                total["topk_readout"] += work.topk_readout_work(self.geometry, kpr)
        return total

    def completed_in(self, t0: float, t1: float) -> int:
        return sum(1 for r in self.requests if t0 <= r.done <= t1 and r.result)

    # -- correctness --------------------------------------------------------

    def sample(self) -> list[Request]:
        """Requests to check, drawn from the seed: half on ideal tenants,
        half on physical ones where both exist."""
        n = int(self.cfg["check_requests"])
        rng = traffic.rng(self.seed, 4)
        done = [r for r in self.in_window() if r.result is not None]
        by_fid: dict[str, list[Request]] = {}
        for r in done:
            by_fid.setdefault(self.fids[r.tenant]["name"], []).append(r)
        picked = []
        for reqs in by_fid.values():
            k = min(len(reqs), max(1, n // len(by_fid)))
            picked += [reqs[i] for i in sorted(rng.choice(len(reqs), k, replace=False))]
        return picked

    def check(self, served=None) -> dict:
        """Compare the sampled answers with the reference.  ``served``
        maps a request to (scores, frames) in place of the program's
        answer (the control)."""
        limits = self.cfg["limits"]
        reqs = self.sample()
        self.checked = len(reqs)
        ref = SearchReference(self.streams, self.kernels, self.fids)
        dets = ref.detections((r.stream, r.tenant) for r in reqs)
        score_err = 0.0 if reqs else float("inf")  # nothing answered
        frame_miss = 0
        for r in reqs:
            d = dets[(r.stream, r.tenant)]
            if served is None:
                s = np.asarray(r.result["scores"])[0]
                f = np.asarray(r.result["peak_frame"])[0]
            else:
                s, f = served(r)
            score_err = max(score_err, float(np.max(np.abs(s - d.peak) / d.scale)))
            o = np.arange(len(f))
            tie = d.per_frame[o, f] >= d.peak - limits["score_err"] * d.scale
            frame_miss += int(np.sum((f != d.frame) & ~tie))
        attempted, failed = self.attempted_failed()
        return {
            "score_err": (score_err, limits["score_err"]),
            "frame_miss": (frame_miss, 0),
            "unanswered": (failed, 0),
        }

    def control_answer(self, r: Request):
        return control.search_detections(
            self.streams[r.stream], self.kernels[r.tenant], self.fids[r.tenant]
        )
