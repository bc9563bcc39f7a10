"""Operations and bytes each measured layer needs, counted from shapes.

These are the layers' own mathematics: a share of a peak or a roofline
reads the same work whatever implements it.  The whole-step counts are
the direct 3-D correlation a request asks for (valid positions × kernels
× taps × 2); the kernel counts are the useful part of the overlap-save
pass at its transform grid, so padding a kernel computes shows up as a
lower roofline share, not as more work.

The grid and window arithmetic below is a copy of the overlap-save plan
(5-smooth FFT sizes ≥ signal + kernel − 1, windows of ``window_frames``
stepping by ``window_frames − kt + 1``), kept here so that the yardstick
does not move when the program does.
"""

from __future__ import annotations

import dataclasses

F32 = 4
COMPLEX_F32 = 8  # a split (real, imaginary) float32 pair


def next_fast_len(n: int) -> int:
    """Smallest 5-smooth integer ≥ n."""
    best = 1
    while best < n:
        best *= 2
    p5 = 1
    while p5 < best:
        p35 = p5
        while p35 < best:
            x = p35
            while x < n:
                x *= 2
            best = min(best, x)
            p35 *= 3
        p5 *= 5
    return best


@dataclasses.dataclass(frozen=True)
class StreamGeometry:
    """One stream searched against ``kh×kw×kt`` kernels in windows."""

    frame_hw: tuple[int, int]
    frames: int
    kernel: tuple[int, int, int]
    window_frames: int
    chunk_windows: int
    channels: int = 1

    @property
    def valid(self) -> tuple[int, int, int]:
        (h, w), (kh, kw, kt) = self.frame_hw, self.kernel
        return (h - kh + 1, w - kw + 1, self.frames - kt + 1)

    @property
    def step(self) -> int:
        return self.window_frames - self.kernel[2] + 1

    @property
    def n_windows(self) -> int:
        return -(-self.valid[2] // self.step)

    @property
    def n_launches(self) -> int:
        return -(-self.n_windows // min(self.chunk_windows, self.n_windows))

    @property
    def bins(self) -> int:
        """Half-spectrum bins of one window's transform grid."""
        (h, w), (kh, kw, kt) = self.frame_hw, self.kernel
        fh = next_fast_len(h + kh - 1)
        fw = next_fast_len(w + kw - 1)
        ft = next_fast_len(self.window_frames + kt - 1)
        return fh * fw * (ft // 2 + 1)


def direct_correlation_flops(
    frame_hw, frames: int, kernel, n_kernels: int, channels: int = 1
) -> int:
    """Multiply-adds of a valid 3-D correlation, counted as 2 each."""
    (h, w), (kh, kw, kt) = frame_hw, kernel
    positions = (h - kh + 1) * (w - kw + 1) * (frames - kt + 1)
    return 2 * positions * n_kernels * channels * kh * kw * kt


def classifier_flops(cfg: dict) -> int:
    """One clip through the hybrid 3-D CNN: the conv layer as a direct
    correlation plus the two dense layers of the head."""
    conv = direct_correlation_flops(
        (cfg["height"], cfg["width"]), cfg["frames"],
        (cfg["k_h"], cfg["k_w"], cfg["k_t"]), cfg["num_kernels"],
        cfg["in_channels"],
    )
    return conv + 2 * pooled_features(cfg) * cfg["hidden"] + 2 * cfg[
        "hidden"
    ] * cfg["num_classes"]


def pooled_features(cfg: dict) -> int:
    oh = cfg["height"] - cfg["k_h"] + 1
    ow = cfg["width"] - cfg["k_w"] + 1
    ot = cfg["frames"] - cfg["k_t"] + 1
    ph, pw, pt = cfg["pool_window"]
    n = ((oh - ph) // ph + 1) * ((ow - pw) // pw + 1) * ((ot - pt) // pt + 1)
    return n * cfg["num_kernels"]


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def min_seconds(self, flops_per_s: float, bytes_per_s: float) -> float:
        """Roofline time: the larger of the compute and memory bounds."""
        return max(self.flops / flops_per_s, self.bytes / bytes_per_s)


ZERO = Work(0.0, 0.0)


def spectral_mac_work(g: StreamGeometry, kernels_per_row: list[int]) -> Work:
    """Pooled spectral MAC of one dispatch: every physical stream row
    against the kernels requested of it, for every window.  A complex
    product is 6 operations and each further channel 2 more to add.
    Bytes: stream spectra in, each row's grating planes once per launch,
    product spectra out."""
    c, f = g.channels, g.bins
    rows, ksum = len(kernels_per_row), sum(kernels_per_row)
    flops = (8 * c - 2) * ksum * g.n_windows * f
    nbytes = COMPLEX_F32 * f * (
        c * rows * g.n_windows + c * ksum * g.n_launches + ksum * g.n_windows
    )
    return Work(float(flops), float(nbytes))


def topk_readout_work(
    g: StreamGeometry, kernels_per_row: list[int], k: int = 1
) -> Work:
    """Fused top-k readout of one dispatch: k compare-and-select passes
    (2 operations each) over every valid score of the stream; bytes are
    those scores and their positions in, and the (score, index) state
    out once per launch."""
    ksum = sum(kernels_per_row)
    oh, ow, ot = g.valid
    positions = oh * ow * ot
    scores = ksum * positions
    flops = 2 * k * scores
    nbytes = F32 * (scores + positions) + 2 * F32 * k * ksum * g.n_launches
    return Work(float(flops), float(nbytes))


def percent_of_roofline(work: Work, seconds: float, peaks) -> float | None:
    """Share (%) of the roofline time in the measured kernel time; None
    when nothing was measured."""
    if seconds <= 0 or work.flops <= 0:
        return None
    return 100.0 * work.min_seconds(
        peaks.flops_per_s, peaks.hbm_bytes_per_s
    ) / seconds

