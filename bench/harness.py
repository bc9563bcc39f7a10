"""One run of one cell: set-up, a measured window, the check against the
plain reference, and the result line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration file (whose ``kind`` names the
driver in ``bench/drivers/``), its traffic mix in ``bench/traffic/``,
and each per-layer metric's reader in ``bench/metrics/<name>.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

from bench import traffic

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
WORK_DIR = BENCH_DIR / ".work"
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/core/compile/jaxpr_trace_duration",
)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def load_spec(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_config(spec: dict, name: str) -> dict:
    """The file of the configuration ``name`` of ``BENCHMARK.json``."""
    entry = {c["name"]: c for c in spec["configs"]}[name]
    with open(ROOT / entry["file"]) as f:
        return json.load(f)


def find_cell(spec: dict, workload: str) -> tuple[dict, dict]:
    """(cell, configuration file) of a workload."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    return cell, load_config(spec, cell["config"])


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def driver_for(cfg: dict):
    return importlib.import_module(f"bench.drivers.{cfg['kind']}")


def reader_for(name: str):
    """``bench/metrics/<name>.py``; metric names may hold dots."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int):
    """The devices of the run; refuses any backend but TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX found {devices[0].platform}; the benchmark measures "
            "only on the chip"
        )
    if len(devices) < n:
        raise NoAccelerator(f"the cell asks for {n} chips; JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``; every program is
    kept, however fast it compiled, so only a cell's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts traces and backend compiles (or cache loads) while on."""

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.counts = {e: 0 for e in COMPILE_EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event in self.counts:
            self.counts[event] += 1

    def summary(self) -> str:
        return (f"compiles in window: backend={self.counts[COMPILE_EVENTS[0]]} "
                f"traces={self.counts[COMPILE_EVENTS[1]]}")

    @property
    def total(self) -> int:
        return sum(self.counts.values())


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader gets."""

    cell: object  # the cell (bench/drivers) after its window
    trace: object | None  # tracing.Trace of the traced window
    peaks: object  # peaks.Peaks of the device


def trace_window(cell, seconds: float, trace: bool):
    """Run the window, under the profiler when ``trace``; returns the
    parsed trace or None."""
    import jax

    if not trace:
        cell.run(seconds)
        return None
    from bench import tracing

    out = WORK_DIR / "trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # spans and device ops, not every call
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            cell.run(seconds)
    finally:
        jax.profiler.stop_trace()
    try:
        return tracing.Trace.from_path(tracing.find_xplane(str(out)))
    finally:
        shutil.rmtree(out, ignore_errors=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, require_tpu: bool = True,
             spec: dict | None = None, cfg: dict | None = None,
             mix: dict | None = None, prepare=None) -> dict:
    """One run.  Tests pass ``require_tpu=False`` and small ``cfg``/``mix``
    to drive everything but the device check on the CPU, and ``prepare``
    to break the served path after set-up."""
    import jax

    spec = spec if spec is not None else load_spec()
    cell_entry, file_cfg = find_cell(spec, workload)
    cfg = cfg if cfg is not None else file_cfg
    mix = mix if mix is not None else traffic.load(cell_entry["traffic"])
    if require_tpu:
        devices = require_chips(int(cell_entry["chips"]))
    else:
        devices = jax.devices()[: int(cell_entry["chips"])]
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    from bench import peaks as peaks_mod

    peaks = None
    if require_tpu:
        peaks = peaks_mod.peaks_for(dev.device_kind)
        log(f"compile cache: {enable_compile_cache()}")
    cell = driver_for(cfg).Cell(cfg, mix, seed, log=log)
    cell.setup()
    if prepare is not None:
        prepare(cell)
    counter = CompileCounter()
    setup_s = time.perf_counter() - t_process
    log(f"setup_s: {setup_s:.3f}")
    counter.on = True
    tr = trace_window(cell, seconds, trace)
    counter.on = False
    log(counter.summary())
    if hasattr(cell, "group_check"):
        g = cell.group_check()
        log(f"batches in window: {g['batches']}, not exactly one group: "
            f"{g['not_one_group']}")
    if getattr(cell, "submit_s", None):
        sub = sorted(cell.submit_s)
        log(f"submit ms per unit: median {1e3 * sub[len(sub) // 2]:.3f} "
            f"p99 {1e3 * sub[int(0.99 * (len(sub) - 1))]:.3f} "
            f"max {1e3 * sub[-1]:.3f} over {len(sub)} units")
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    e2e = cell.end_to_end()
    attempted, failed = cell.attempted_failed()
    metrics: dict = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        ctx = LayerContext(cell=cell, trace=tr, peaks=peaks)
        for m in spec["per_layer"]:
            if applies(m, workload):
                value = reader_for(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = driver_breakdown(cell, tr)
    else:
        for m in spec["end_to_end"]:
            if not applies(m, workload):
                continue
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    cell.close()
    gc.collect()
    t_check = time.perf_counter()
    compared = cell.check()
    log(f"checked {getattr(cell, 'checked', '?')} answers against the "
        f"reference in {time.perf_counter() - t_check:.3f} s")
    correct = all(v <= lim for v, lim in compared.values())
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def driver_breakdown(cell, tr) -> dict:
    gaps = tr.idle_gaps(cell.IDLE_LABELS, cell.IDLE_FALLBACK)
    gaps = sorted(gaps, key=lambda g: g[1] - g[2])[:10]
    return {
        "device_ops": tr.top_ops(10),
        "idle_gaps": [[label, (e - s) * 1e-9] for label, s, e in gaps],
    }


def print_result(result: dict) -> None:
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
