# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  accuracy    — §4.1 + Fig. 6 (classification + confusion matrix)
  speed       — §2/§5 fps table (measured digital vs projected optical)
  equivalence — §4 optical-model validation (ideal + physical error)
  kernels     — Pallas kernel micro-benches vs oracles
  roofline    — §Roofline summary from the dry-run records
  ablation    — §4 degradation decomposition, one fidelity stage at a
                time (also standalone: benchmarks/ablation.py --smoke)
  serving     — pooled cross-tenant executor vs per-tenant-sequential
                + microbatch-scheduler load sweep (also standalone:
                benchmarks/serving.py --smoke)
  chaos       — availability under an injected fault storm: typed-error
                resolution, breaker trip/recover, degraded-rung capacity
                (also standalone: benchmarks/chaos.py --smoke)
  mesh        — device-mesh sharded serving: bitwise-equality audit vs
                single-device + the 8-device scaling row (needs 8 host
                devices; standalone benchmarks/mesh.py forces them,
                through run.py it skips loudly on a 1-device process)

``--fast`` shrinks the accuracy benchmark geometry for CI-speed runs.
``--json`` additionally writes one ``BENCH_<suite>.json`` artifact per
suite (into ``--json-dir``, default CWD) so the perf trajectory — e.g.
fused vs unfused query latency, stmul v1 vs v2 — is recorded per PR.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


def _parse_row(row: str) -> dict:
    """Split a ``name,us_per_call,derived`` CSV row into a JSON record."""
    name, us, derived = row.split(",", 2)
    try:
        us_val: float | str = float(us)
    except ValueError:
        us_val = us
    return {"name": name, "us_per_call": us_val, "derived": derived}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced geometry for the accuracy benchmark")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<suite>.json artifacts")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_*.json artifacts")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        ablation,
        accuracy,
        chaos,
        equivalence,
        kernels_bench,
        mesh,
        roofline_bench,
        serving,
        speed,
    )

    suites = {
        "equivalence": lambda: equivalence.run(log=_log),
        "speed": lambda: speed.run(log=_log),
        "kernels": lambda: kernels_bench.run(log=_log),
        "roofline": lambda: roofline_bench.run(log=_log),
        "accuracy": lambda: accuracy.run(
            epochs=10 if args.fast else 45,
            full_geometry=not args.fast,
            log=_log,
        ),
        "ablation": lambda: ablation.run(
            epochs=2 if args.fast else 45,
            full_geometry=not args.fast,
            log=_log,
        ),
        "serving": lambda: serving.run(smoke=args.fast, log=_log),
        "chaos": lambda: chaos.run(smoke=args.fast, log=_log),
        "mesh": lambda: mesh.run(smoke=args.fast, log=_log),
    }
    if args.only:
        keep = set(args.only.split(","))
        unknown = keep - suites.keys()
        if unknown:
            ap.error(
                f"unknown suite(s) {sorted(unknown)}; "
                f"available: {sorted(suites)}"
            )
        suites = {k: v for k, v in suites.items() if k in keep}
    if args.json:
        os.makedirs(args.json_dir, exist_ok=True)

    failures = 0
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        try:
            rows = list(fn())
            for row in rows:
                print(row, flush=True)
            if args.json:
                path = os.path.join(args.json_dir, f"BENCH_{name}.json")
                with open(path, "w") as f:
                    json.dump(
                        {"suite": name, "rows": [_parse_row(r) for r in rows]},
                        f,
                        indent=2,
                    )
                _log(f"wrote {path}")
        except Exception:
            failures += 1
            print(f"{name}_FAILED,0,error", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


def _log(msg: str) -> None:
    print(f"# {msg}", flush=True)


if __name__ == "__main__":
    # allow `python benchmarks/run.py` from the repo root: sys.path[0]
    # is the script's own directory, so the intra-suite imports
    # (`from benchmarks import ...`) need the root added explicitly
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
