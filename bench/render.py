"""KTH-style synthetic video, rendered at set-up from the seed.

A copy of the program's ``data/kth_synthetic.render_clip`` (four action
classes as moving blobs, per-subject style and noise), vectorised over
frames; the bench test checks it against the original value for value.
Kept here so that the data cannot move under a later change to the
program.

A camera ingest hands the server decoded 8-bit frames, so each clip is
brought to 8-bit levels with its brightest pixel at 255, as float32 in
[0, 1].  That also puts every pixel exactly on the SLM's 8-bit grid.
"""

from __future__ import annotations

import numpy as np

LABELS = 4
SUBJECTS = range(1, 26)
SCENARIOS = 4


def _blob(h, w, cy, cx, ry, rx):
    """Gaussian blob over (T, h, w); centres are per-frame arrays."""
    yy = np.arange(h)[None, :, None]
    xx = np.arange(w)[None, None, :]
    cy = np.asarray(cy, np.float64).reshape(-1, 1, 1)
    cx = np.asarray(cx, np.float64).reshape(-1, 1, 1)
    return np.exp(-(((yy - cy) / ry) ** 2 + ((xx - cx) / rx) ** 2))


def render_clip(label: int, subject: int, scenario: int, h: int, w: int,
                frames: int) -> np.ndarray:
    """One (H, W, T) float32 clip in [0, 1]."""
    rng = np.random.RandomState(subject * 1009 + scenario * 101 + label)
    T = frames
    scale = 0.8 + 0.4 * rng.rand()
    speed = 0.7 + 0.6 * rng.rand()
    phase = 2 * np.pi * rng.rand()
    cx0 = w * (0.35 + 0.3 * rng.rand())
    cy0 = h * (0.45 + 0.15 * rng.rand())
    noise = 0.02 + 0.03 * rng.rand()
    bg = 0.1 + 0.08 * rng.rand()
    t = np.arange(T)
    tt = speed * t + phase
    one = np.ones(T)
    frame = np.full((T, h, w), bg, np.float32)
    if label == 3:  # running: global translation + limb oscillation
        gx = (cx0 + (t - T / 2) * 2.2 * speed) % w
        leg = 5 * scale * np.sin(2.2 * tt)
        frame += 0.5 * _blob(h, w, cy0 * one, gx, 8 * scale, 3.5 * scale)
        frame += 0.45 * _blob(h, w, (cy0 - 11 * scale) * one, gx + 1, 3.2, 2.8)
        frame += 0.5 * _blob(h, w, (cy0 + 9 * scale) * one, gx + leg, 3, 2.2)
        frame += 0.5 * _blob(h, w, (cy0 + 9 * scale) * one, gx - leg, 3, 2.2)
    else:
        frame += 0.5 * _blob(h, w, cy0 * one, cx0 * one, 9 * scale, 4 * scale)
        frame += 0.45 * _blob(
            h, w, (cy0 - 12 * scale) * one, cx0 * one, 3.5 * scale, 3 * scale
        )
        if label == 0:  # clapping: hands oscillate toward the midline
            dx = 6 * scale * np.abs(np.sin(1.8 * tt))
            for s in (-1, 1):
                frame += 0.6 * _blob(
                    h, w, (cy0 - 2 * scale) * one, cx0 + s * (4 + dx), 2.5, 2.5
                )
        elif label == 1:  # waving: hands swing vertically overhead
            dy = 7 * scale * np.sin(0.9 * tt)
            for s in (-1, 1):
                frame += 0.6 * _blob(
                    h, w, cy0 - 14 * scale - dy * s, (cx0 + s * 9 * scale) * one,
                    2.5, 2.5,
                )
        else:  # boxing: one fist thrusts forward (sawtooth)
            saw = (0.9 * tt / np.pi) % 1.0
            thrust = 12 * scale * np.where(saw < 0.3, saw, (1 - saw) * 0.43)
            frame += 0.65 * _blob(
                h, w, (cy0 - 4 * scale) * one, cx0 + 5 + thrust, 2.5, 3.0
            )
            frame += 0.5 * _blob(
                h, w, (cy0 - 2 * scale) * one, (cx0 - 5 * scale) * one, 2.5, 2.5
            )
    frame += noise * rng.randn(T, h, w).astype(np.float32)
    return np.ascontiguousarray(np.clip(frame, 0.0, 1.0).transpose(1, 2, 0))


def to_8bit(clip: np.ndarray) -> np.ndarray:
    """Decoded 8-bit frames as float32 in [0, 1], brightest pixel 255."""
    q = np.round(clip.astype(np.float64) / float(clip.max()) * 255.0)
    return (q / 255.0).astype(np.float32)


def _subjects(rng: np.random.Generator, n: int) -> list[tuple[int, int, int]]:
    """n distinct (label, subject, scenario) triples: no two pool entries
    hash alike, so only the traffic decides what is shared."""
    combos = [
        (label, subject, scen)
        for label in range(LABELS)
        for subject in SUBJECTS
        for scen in range(SCENARIOS)
    ]
    if n > len(combos):
        raise ValueError(f"at most {len(combos)} distinct clips, asked for {n}")
    return [combos[i] for i in rng.choice(len(combos), size=n, replace=False)]


def stream_pool(rng: np.random.Generator, n: int, frame_hw, frames: int):
    """n distinct (1, 1, H, W, T) float32 streams."""
    h, w = frame_hw
    return [
        to_8bit(render_clip(*c, h, w, frames))[None, None]
        for c in _subjects(rng, n)
    ]


def clip_batches(rng: np.random.Generator, n_batches: int, clips: int,
                 frame_hw, frames: int):
    """n_batches arrays of shape (clips, 1, H, W, T), no clip repeated."""
    h, w = frame_hw
    subs = _subjects(rng, n_batches * clips)
    out = []
    for b in range(n_batches):
        batch = np.stack([
            to_8bit(render_clip(*c, h, w, frames))[None]
            for c in subs[b * clips:(b + 1) * clips]
        ])
        out.append(np.ascontiguousarray(batch))
    return out
