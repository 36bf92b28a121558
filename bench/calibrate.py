#!/usr/bin/env python3
"""Readings for a cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload gcn-ogbn-arxiv.fwd-f32 \\
        --seeds 101 102 103 --seconds 2 [--out readings.json]

Runs the cell once per seed in this one process (compiled programs are
shared after the first), each with a short window at the cell's own
size, and reads both sides of every compared number: the program against
the plain reference (the lower reading) and the mix's control, the
reference at the lower precision in the program's place (the upper
reading).  The benchmark's own runs never run the control.  Prints one
JSON line per seed, and the largest program and smallest control reading
of each number at the end.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    spec = run.spec_of(args.workload)
    try:
        run.find_devices(spec["cell"]["chips"], True)
    except run.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    rows = []
    for seed in args.seeds:
        r = run.run_cell(spec, seed, args.seconds, False, control=True,
                         log=lambda *a, **k: None)
        row = {"seed": seed, "correct": r["correct"],
               "program": {k: v["value"] for k, v in r["checks"].items()},
               "control": {k: r["control"][k]["value"] for k in run.CHECKED},
               "diagnostics": r["diagnostics"],
               "forward_ms": r["metrics"]["forward_ms"]["value"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {name: {"program_max": max(r["program"][name] for r in rows),
                      "control_min": min(r["control"][name] for r in rows)}
               for name in run.CHECKED}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
