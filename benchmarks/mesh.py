"""Device-mesh sharded serving benchmark — the pooled grating arena
over the ``model`` axis and the stream fan-out over the ``data`` axis
of a ``(data, model)`` mesh, vs the single-device pooled executor.

Needs 8 host devices.  Run standalone the module forces them itself
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be set
before jax initializes — CI's mesh-smoke job exports it at the job
level); through ``benchmarks/run.py`` on an already-initialized
1-device process the suite degrades to a loud skip row.

What is measured — and what deliberately is not:

* ``mesh_exact_*`` — bitwise-equality audit rows: the max absolute
  difference between sharded and single-device scores across the
  serving surface (stitched volumes, fused top-K, shared-stream dedup,
  bf16 storage, chunked StreamCursor).  The acceptance invariant is
  ``max_abs_err == 0.0`` (gated ``eq``) — the sharded executor reuses
  the single-device op sequence per shard, so equality is exact, not
  approximate.
* ``mesh_scaling_d8`` — the scaling row at 8 devices.  This container
  serves all 8 forced host devices from ONE physical core, so a
  wall-clock speedup is structurally impossible here; what the row
  pins instead is (a) the **analytic per-device scaling** — how much
  less arena + MAC work each device holds vs the single-device pool
  (deterministic, from the shard-tiled packing itself) — and (b) the
  measured **throughput-parity ratio** (sharded windows/s over
  single-device windows/s on the same host): the sharded dispatch must
  not collapse under partitioning overhead.  On real multi-core/TPU
  hosts the analytic row is the speedup ceiling.
* ``mesh_stream_d8`` / ``mesh_single`` — the raw windows/s of both
  paths (absolute, machine-local; the gate only reads the ratio).

Run standalone (writes ``BENCH_mesh.json``)::

    PYTHONPATH=src python benchmarks/mesh.py [--smoke] [--json-dir .]

or as a suite through ``benchmarks/run.py --only mesh``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

if __name__ == "__main__":
    # standalone: force the host-device fan-out before jax initializes
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fidelity as fid
from repro.core.sthc import STHC, STHCConfig

# serving geometry: mixed-tenant kernel banks over one window shape —
# wide enough that the arena actually tiles (ΣO=14 over 4 model shards)
FRAME_HW = (20, 24)
KERNEL_HW_T = (7, 9, 4)
TENANT_O = (3, 5, 2, 4)
TENANT_B = (2, 1, 3, 2)
STREAM_T = 64
MESH_SHAPE = (2, 4)  # (data, model) — 8 devices
CHUNK_WINDOWS = 2
READOUT_K = 3


def _fmt(v: float) -> str:
    return f"{v:.2f}" if abs(v) >= 0.01 or v == 0 else f"{v:.2e}"


def _row(name: str, us: float, derived: dict | str) -> str:
    if isinstance(derived, dict):
        derived = ";".join(f"{k}={_fmt(v)}" for k, v in derived.items())
    return f"{name},{us:.0f},{derived}"


def _engine(**over):
    cfg = dict(fidelity=fid.physical(), osave_chunk_windows=CHUNK_WINDOWS)
    cfg.update(over)
    return STHC(STHCConfig(**cfg)).engine


def _requests(eng, T=STREAM_T):
    kh, kw, kt = KERNEL_HW_T
    h, w = FRAME_HW
    reqs = []
    for i, (o, b) in enumerate(zip(TENANT_O, TENANT_B)):
        k = jnp.asarray(
            np.random.RandomState(i).randn(o, 1, kh, kw, kt).astype(np.float32)
        )
        x = jnp.asarray(
            np.random.RandomState(100 + i).rand(b, 1, h, w, T).astype(
                np.float32
            )
        )
        reqs.append((eng.record(k, x.shape[-3:]), x))
    return reqs


def _max_err(ref, got) -> tuple[float, int]:
    """max |a−b| and mismatch count over a pytree pair."""
    err, n = 0.0, 0
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        d = jnp.abs(
            jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)
        )
        err = max(err, float(jnp.max(d)))
        n += int(jnp.sum(d > 0))
    return err, n


def _topk_tree(dets):
    return [(d.scores, d.index) for d in dets]


def _exactness_rows(mesh, log) -> list[str]:
    rows = []

    def audit(name, ref, got):
        err, n = _max_err(ref, got)
        rows.append(_row(f"mesh_exact_{name}", 0, {
            "max_abs_err": err, "mismatches": float(n),
        }))
        log(f"mesh_exact_{name}: max_abs_err={err} mismatches={n}")

    eng = _engine()
    reqs = _requests(eng)
    audit(
        "volume",
        eng.query_stream_many(reqs, dedup=True),
        eng.query_stream_many(reqs, dedup=True, mesh=mesh),
    )
    audit(
        "fused_topk",
        _topk_tree(eng.query_stream_many(reqs, dedup=True, readout_k=READOUT_K)),
        _topk_tree(
            eng.query_stream_many(
                reqs, dedup=True, readout_k=READOUT_K, mesh=mesh
            )
        ),
    )
    shared = reqs[0][1]
    shared_reqs = [(g, shared) for g, _ in reqs]
    audit(
        "dedup",
        _topk_tree(
            eng.query_stream_many(shared_reqs, dedup=True, readout_k=READOUT_K)
        ),
        _topk_tree(
            eng.query_stream_many(
                shared_reqs, dedup=True, readout_k=READOUT_K, mesh=mesh
            )
        ),
    )
    audit(
        "chunked",
        _topk_tree(
            eng.query_stream_many(
                reqs, dedup=True, readout_k=READOUT_K, max_buffer_windows=3
            )
        ),
        _topk_tree(
            eng.query_stream_many(
                reqs, dedup=True, readout_k=READOUT_K,
                max_buffer_windows=3, mesh=mesh,
            )
        ),
    )
    eng16 = _engine(grating_dtype="bfloat16")
    reqs16 = _requests(eng16)
    audit(
        "bf16",
        _topk_tree(
            eng16.query_stream_many(reqs16, dedup=True, readout_k=READOUT_K)
        ),
        _topk_tree(
            eng16.query_stream_many(
                reqs16, dedup=True, readout_k=READOUT_K, mesh=mesh
            )
        ),
    )
    return rows


def _scaling_rows(mesh, reps: int, log) -> list[str]:
    from repro.core import engine as engine_mod

    rows = []
    eng = _engine()
    reqs = _requests(eng)
    gs = [g for g, _ in reqs]
    b_total = sum(int(x.shape[0]) for _, x in reqs)
    plan = eng.stream_plan_for(gs[0], STREAM_T, None)
    windows = plan.n_blocks * b_total

    # analytic per-device scaling, from the shard-tiled packing itself
    d, m = MESH_SHAPE
    align, _ = eng._slot_layout(gs)
    pool1 = engine_mod._build_pool(gs, align, 1)
    poolm = engine_mod._build_pool(gs, align, m)
    rows_single = int(pool1.re.shape[0])
    rows_per_dev = poolm.shard_rows
    b_per_dev = -(-b_total // d)
    work_x = (b_total * rows_single) / (b_per_dev * rows_per_dev)
    arena_x = rows_single / rows_per_dev

    # measured windows/s, both paths warmed and interleaved (shared-host
    # noise hits both equally)
    for use_mesh in (True, False):
        eng.query_stream_many(
            reqs, dedup=True, readout_k=READOUT_K,
            mesh=mesh if use_mesh else None,
        )
    lats: dict[bool, list[float]] = {True: [], False: []}
    for _ in range(reps):
        for use_mesh in (False, True):
            t0 = time.perf_counter()
            out = eng.query_stream_many(
                reqs, dedup=True, readout_k=READOUT_K,
                mesh=mesh if use_mesh else None,
            )
            jax.block_until_ready([d.scores for d in out])
            lats[use_mesh].append(time.perf_counter() - t0)
    winps = {
        k: windows / statistics.median(v) for k, v in lats.items()
    }
    parity = winps[True] / winps[False]
    rows.append(_row("mesh_stream_d8", 1e6 * statistics.median(lats[True]), {
        "windows_per_s": winps[True],
    }))
    rows.append(_row("mesh_single", 1e6 * statistics.median(lats[False]), {
        "windows_per_s": winps[False],
    }))
    rows.append(_row("mesh_scaling_d8", 0, {
        "devices": float(d * m),
        "data": float(d),
        "model": float(m),
        "per_device_work_x": work_x,
        "arena_per_device_x": arena_x,
        "winps_parity_x": parity,
    }))
    log(
        f"mesh_scaling_d8: per-device work {work_x:.2f}x lighter, arena "
        f"{arena_x:.2f}x smaller, throughput parity {parity:.2f}x "
        f"({winps[True]:.0f} vs {winps[False]:.0f} win/s on this host)"
    )
    return rows


def run(smoke: bool = False, log=print) -> list[str]:
    if jax.device_count() < 8:
        # run.py path on an already-initialized single-device process:
        # the mesh suite cannot re-fan-out the host — loud skip row so
        # the artifact never silently records a 1-device "mesh" result
        log(
            "mesh suite SKIPPED: needs 8 host devices (set XLA_FLAGS="
            '"--xla_force_host_platform_device_count=8" before jax '
            "initializes, or run benchmarks/mesh.py standalone)"
        )
        return [
            _row("mesh_skipped", 0, {"devices": float(jax.device_count())})
        ]
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(*MESH_SHAPE)
    reps = 9 if smoke else 25
    rows = _exactness_rows(mesh, log)
    rows += _scaling_rows(mesh, reps, log)
    return rows


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    try:
        us_val: float | str = float(us)
    except ValueError:
        us_val = us
    return {"name": name, "us_per_call": us_val, "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced reps (the CI mesh-smoke)",
    )
    ap.add_argument(
        "--json-dir", default=".", help="directory for BENCH_mesh.json"
    )
    args = ap.parse_args()
    rows = run(smoke=args.smoke, log=print)
    print("name,us_per_call,derived")
    for row in rows:
        print(row)
    os.makedirs(args.json_dir, exist_ok=True)
    path = os.path.join(args.json_dir, "BENCH_mesh.json")
    with open(path, "w") as f:
        json.dump(
            {"suite": "mesh", "rows": [_parse_row(r) for r in rows]},
            f,
            indent=2,
        )
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
