"""The comparison that decides ``correct`` fails a broken served path:
an answer altered where it is produced, half of a batch left out, and
the control — the reference at three-bfloat16-pass precision in the
program's place.  (One chip: no exchange between chips to leave out;
serving: no state to leave unchanged.)"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

from bench.tests import _tiny


def _alter_search_answers(cell):
    eng = cell.server.sthc.engine
    inner = eng.query_stream_many

    def altered(requests, **kw):
        return [dataclasses.replace(d, scores=d.scores * 1.0001)
                for d in inner(requests, **kw)]

    eng.query_stream_many = altered


def _half_search_batch(cell):
    inner = cell.server.search_batch

    def half(requests, **kw):
        n = len(requests) // 2
        out = inner(requests[:n], **kw)
        return out + out[: len(requests) - n]

    cell.server.search_batch = half


def _alter_logits(cell):
    inner = cell.server._head
    cell.server._head = lambda conv: inner(conv) * 1.0001


def _half_clip_batch(cell):
    inner = cell.server.logits

    def half(clips):
        n = clips.shape[0] // 2
        got = inner(clips[:n])
        return jnp.concatenate([got, got[: clips.shape[0] - n]])

    cell.server.logits = half


@pytest.mark.parametrize("workload,fault", [
    ("search-fanout-saturate", _alter_search_answers),
    ("search-fanout-saturate", _half_search_batch),
    ("classify-b32", _alter_logits),
    ("classify-b32", _half_clip_batch),
])
def test_broken_path_is_not_correct(workload, fault):
    broken = _tiny.run(workload, seconds=3.0, prepare=fault)
    assert not broken["correct"], broken["compared"]
    failing = [c["value"] for c in broken["compared"].values()
               if c["value"] > c["limit"]]
    assert all(v < float("inf") for v in failing)  # answers were compared


def _control_cfg(workload: str) -> dict:
    """The control's gap grows with a kernel's taps, as its
    pseudo-negative halves cancel more: at the smallest test size (72
    taps) it reads near the limits set for 9,600, so the control is kept
    at 320, with the search's 9 kernels per tenant."""
    cfg = _tiny.cfg(workload)
    if workload.startswith("classify"):
        cfg.update(height=20, width=24, frames=8, k_h=8, k_w=10, k_t=4)
    else:
        cfg.update(frame_hw=[20, 24], stream_frames=32, kernel_shape=[8, 10, 4],
                   kernels_per_tenant=9, check_requests=16)
        cfg["server"].update(window_frames=16)
    return cfg


@pytest.mark.parametrize("workload", ["search-fanout-saturate", "classify-b32"])
def test_control_is_not_correct(workload):
    cell = _tiny.cell(workload, config=_control_cfg(workload))
    cell.run(1.0)
    cell.close()
    program = cell.check()
    control = cell.check(served=cell.control_answer)
    assert all(v <= lim for v, lim in program.values()), program
    assert not all(v <= lim for v, lim in control.values()), control
