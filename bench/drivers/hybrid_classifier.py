"""Driver for configurations of kind ``hybrid_classifier``: the paper's
hybrid 3-D CNN served by ``HybridClassifierServer.logits``, the logits
copied back to the host as a client reads them."""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import render, traffic, work
from bench.drivers.video_search import program_pipeline
from bench.reference import control
from bench.reference import hybrid_classifier as reference


@dataclasses.dataclass
class Request:
    unit: int
    batch: int  # pool entry: one array of clips
    sent: float
    done: float = float("nan")
    logits: np.ndarray | None = None


def make_params(seed: int, cfg: dict) -> dict:
    """Weights made on the device in one call from the seed: normal
    draws at the initial scale of the paper's training, the conv kernels
    on the SLM's signed levels with a range of their own per kernel."""
    import jax
    import jax.numpy as jnp

    levels = float(2 ** cfg["kernel_bits"] - 1)
    conv = (cfg["num_kernels"], cfg["in_channels"], cfg["k_h"], cfg["k_w"],
            cfg["k_t"])
    feat = work.pooled_features(cfg)
    hid, ncls = cfg["hidden"], cfg["num_classes"]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6)
        w = jax.random.normal(ks[0], conv, jnp.float32)
        rng_ = jnp.max(jnp.abs(w), axis=(1, 2, 3, 4), keepdims=True)
        w = jnp.round(w / rng_ * levels) * (rng_ / levels)
        w = w * jnp.sqrt(2.0 / np.prod(conv[1:]))
        return {
            "conv_w": w,
            "conv_b": 0.1 * jax.random.normal(ks[1], (conv[0],), jnp.float32),
            "fc1_w": jax.random.normal(ks[2], (feat, hid), jnp.float32)
            * jnp.sqrt(2.0 / feat),
            "fc1_b": 0.1 * jax.random.normal(ks[3], (hid,), jnp.float32),
            "fc2_w": jax.random.normal(ks[4], (hid, ncls), jnp.float32)
            * jnp.sqrt(2.0 / hid),
            "fc2_b": 0.1 * jax.random.normal(ks[5], (ncls,), jnp.float32),
        }

    return make(jax.random.key(int(traffic.rng(seed, 5).integers(2**31))))


class Cell:
    IDLE_LABELS = (
        ("bench.classify", "logits call host part"),
    )
    IDLE_FALLBACK = "client between calls"

    def __init__(self, cfg: dict, mix: dict, seed: int, log=print):
        self.cfg, self.mix, self.seed, self.log = cfg, mix, int(seed), log
        self.fid = cfg["fidelities"][cfg["fidelity"]]
        self.schedule = traffic.Schedule(mix, seed)
        self.requests: list[Request] = []
        self.window = (0.0, 0.0)

    def setup(self) -> None:
        import jax

        from repro.core.hybrid import HybridConfig
        from repro.launch.serve import HybridClassifierServer

        cfg = self.cfg
        self.params = make_params(self.seed, cfg)
        self.batches = render.clip_batches(
            traffic.rng(self.seed, 3), self.mix["pool"],
            self.mix["clips_per_request"], (cfg["height"], cfg["width"]),
            cfg["frames"],
        )
        hcfg = HybridConfig(
            height=cfg["height"], width=cfg["width"], frames=cfg["frames"],
            in_channels=cfg["in_channels"], num_kernels=cfg["num_kernels"],
            k_h=cfg["k_h"], k_w=cfg["k_w"], k_t=cfg["k_t"],
            pool_window=tuple(cfg["pool_window"]), hidden=cfg["hidden"],
            num_classes=cfg["num_classes"],
        )
        self.server = HybridClassifierServer(
            self.params, hcfg, fidelity=program_pipeline(self.fid)
        )
        for i in range(2):
            self._serve(i)
        self.requests.clear()

    def _serve(self, i: int) -> Request:
        import jax

        (_, b), = self.schedule.unit(i)
        req = Request(unit=i, batch=b, sent=time.perf_counter())
        # the configuration states float32 arithmetic; JAX's default on a
        # TPU is one bfloat16 pass, so the deployment asks for it
        with jax.profiler.TraceAnnotation("bench.classify"), \
                jax.default_matmul_precision(self.cfg["matmul_precision"]):
            req.logits = np.asarray(self.server.logits(self.batches[b]))
        req.done = time.perf_counter()
        self.requests.append(req)
        return req

    def run(self, seconds: float) -> None:
        t0 = time.perf_counter()
        end = t0 + seconds
        i = 0
        while time.perf_counter() < end:
            self._serve(i)
            i += 1
        self.window = (t0, end)

    def close(self) -> None:
        self.server = None

    def in_window(self) -> list[Request]:
        return [r for r in self.requests if r.done <= self.window[1]]

    def attempted_failed(self) -> tuple[int, int]:
        sent = [r for r in self.requests if r.sent < self.window[1]]
        return len(sent), sum(1 for r in sent if r.logits is None)

    def end_to_end(self) -> dict:
        clips = self.mix["clips_per_request"]
        n = len(self.in_window())
        t0, end = self.window
        return {"classify_frames_per_s": n * clips * self.cfg["frames"] / (end - t0)}

    def completed_in(self, t0: float, t1: float) -> int:
        return sum(1 for r in self.requests if t0 <= r.done <= t1)

    # -- correctness --------------------------------------------------------

    def sample(self) -> list[Request]:
        reqs = self.in_window()
        n = min(len(reqs), int(self.cfg["check_requests"]))
        rng = traffic.rng(self.seed, 4)
        return [reqs[i] for i in sorted(rng.choice(len(reqs), n, replace=False))]

    def check(self, served=None) -> dict:
        """Largest logit gap of the sampled requests from the reference,
        relative to the request's largest |reference logit|."""
        limits = self.cfg["limits"]
        params = {k: np.asarray(v) for k, v in self.params.items()}
        reqs = self.sample()
        self.checked = len(reqs) * self.mix["clips_per_request"]
        refs: dict[int, np.ndarray] = {}
        err = 0.0 if reqs else float("inf")  # nothing answered: not shown
        for r in reqs:
            if r.batch not in refs:
                refs[r.batch] = reference.logits(
                    params, self.batches[r.batch], self.cfg, self.fid
                )
            ref = refs[r.batch]
            got = r.logits if served is None else served(r)
            err = max(err, float(np.max(np.abs(got - ref)) / np.max(np.abs(ref))))
        attempted, failed = self.attempted_failed()
        return {
            "logit_err": (err, limits["logit_err"]),
            "unanswered": (failed, 0),
        }

    def control_answer(self, r: Request):
        params = {k: np.asarray(v) for k, v in self.params.items()}
        return control.classifier_logits(
            params, self.batches[r.batch], self.cfg, self.fid
        )
