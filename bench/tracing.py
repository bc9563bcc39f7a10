"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are ``/device:<KIND>:<n>``; their ``XLA Ops``
line holds one event per operation run on the device.  Host threads are
lines of ``/host:CPU``; the harness's own spans (``bench.*``, written
with ``jax.profiler.TraceAnnotation``) are events there, on the same
clock.  ``bench.window`` spans the measured window.

- busy: the union of operation intervals on a device, clipped to the
  window, averaged over the devices used; idle is the rest.
- kernel time: the summed device durations of a Pallas kernel's
  operations.  On a TPU the kernel is a ``tpu_custom_call`` named after
  the jitted function that makes the ``pallas_call`` (under ``vmap``
  ``vmap_jit_<name>__.<n>``), and its op name ends in
  ``jit(<name>)/pallas_call``; the kernel's own function name does not
  appear.  An operation counts when its name holds that function's
  name, or when its HLO text is a ``tpu_custom_call`` that does — not
  the pads and copies the same function makes around the call.
- idle gaps: the intervals of the window with no operation running,
  each named by the harness span that covers most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
NAME_STATS = ("hlo_op", "long_name", "tf_op", "name")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns
    detail: str = ""  # the event's naming stats, for matching kernels


def _naming(ev) -> str:
    parts = []
    for key, val in ev.stats:
        if key in NAME_STATS:
            parts.append(str(val))
    return " ".join(parts)


def load_profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


class Trace:
    """The parts of one trace the metrics need."""

    def __init__(self, profile):
        self.devices: dict[str, list[Event]] = {}
        self.host: list[Event] = []
        for plane in profile.planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name:
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        self.devices.setdefault(plane.name, []).extend(
                            Event(e.name, int(e.start_ns), int(e.end_ns),
                                  _naming(e))
                            for e in line.events
                        )
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    self.host.extend(
                        Event(e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events
                        if e.name.startswith("bench.")
                    )
        windows = [e for e in self.host if e.name == WINDOW_SPAN]
        if windows:
            self.window = (windows[0].start, windows[-1].end)
        else:
            every = [e for evs in self.devices.values() for e in evs]
            self.window = (min(e.start for e in every), max(e.end for e in every))

    @classmethod
    def from_path(cls, path: str) -> "Trace":
        return cls(load_profile(path))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: str) -> list[tuple[int, int]]:
        evs = self.devices[device]
        return _clip(_union((e.start, e.end) for e in evs), *self.window)

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = 0
        for dev in self.devices:
            tot += sum(e - s for s, e in self.busy_intervals(dev))
        return tot * 1e-9 / len(self.devices)

    def _in_window(self):
        lo, hi = self.window
        for evs in self.devices.values():
            for e in evs:
                if e.end > lo and e.start < hi:
                    yield e

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the Pallas kernel made by the function
        ``kernel``, summed over devices."""
        return 1e-9 * sum(
            e.end - e.start for e in self._in_window()
            if kernel in e.name
            or (kernel in e.detail and "tpu_custom_call" in e.detail)
        )

    def top_ops(self, n: int = 10) -> list[list]:
        tot: dict[str, int] = {}
        for e in self._in_window():
            tot[e.name] = tot.get(e.name, 0) + e.end - e.start
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]

    def spans(self, name: str) -> list[tuple[int, int]]:
        return [(e.start, e.end) for e in self.host if e.name == name]

    def idle_gaps(self, labels, fallback: str) -> list[tuple[str, int, int]]:
        """Every idle interval of the first device in the window, named by
        the label of the (span name, label) pair whose spans cover most
        of it, or ``fallback`` where none does."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(dev):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        spans = [(_union(self.spans(name)), lab) for name, lab in labels]
        out = []
        for s, e in gaps:
            best, label = 0, fallback
            for iv, lab in spans:
                cover = sum(max(0, min(e, b) - max(s, a)) for a, b in iv)
                if cover > best:
                    best, label = cover, lab
            out.append((label, s, e))
        return out

    def overlap_busy_s(self, intervals) -> float:
        """Device-busy seconds inside the given host intervals (first
        device)."""
        if not self.devices:
            return 0.0
        busy = self.busy_intervals(sorted(self.devices)[0])
        tot = 0
        for a, b in intervals:
            tot += sum(max(0, min(b, e) - max(a, s)) for s, e in busy)
        return tot * 1e-9
