#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload search-fanout-saturate \
        --seeds 1,2,3 --seconds 4 [--out control.jsonl]

For each seed, in one process: set the cell up, run a short window at
the cell's own load, then compare the sampled answers with the plain
reference twice — once the program's (the lower reading) and once the
control's, the reference itself at three-bfloat16-pass precision put in
the program's place (the upper reading).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def readings(cell, seconds: float) -> dict:
    cell.setup()
    cell.run(seconds)
    cell.close()
    program = cell.check()
    control = cell.check(served=cell.control_answer)
    return {
        "seed": cell.seed,
        "checked": cell.checked,
        "program": {k: v for k, (v, _) in program.items()},
        "control": {k: v for k, (v, _) in control.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import harness, traffic

    spec = harness.load_spec()
    cell_entry, cfg = harness.find_cell(spec, args.workload)
    mix = traffic.load(cell_entry["traffic"])
    try:
        harness.require_chips(int(cell_entry["chips"]))
    except harness.NoAccelerator as exc:
        harness.log(f"control: {exc}")
        return 2
    harness.enable_compile_cache()
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = harness.driver_for(cfg).Cell(cfg, mix, seed, log=harness.log)
            line = json.dumps({"workload": args.workload, **readings(cell, args.seconds)})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
