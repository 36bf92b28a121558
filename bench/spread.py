#!/usr/bin/env python3
"""Run a cell several times, one process a run, and report the spread.

    python3 bench/spread.py --workload gcn-ogbn-arxiv.fwd-f32 \\
        --seeds 1 2 3 4 5 6 --sets 2 --seconds 10 [--trace 0] [--out f.json]

Each set runs ``bench/run.py`` once per seed, in order, with the same
seeds in every set.  For each end-to-end metric it prints each set's
median and spread: the distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` as a share of the median.  This
process never touches JAX, so each run has the chip to itself.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parent / "run.py"


def spread(values) -> dict:
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return {"median": med, "q1": q1, "q3": q3,
            "spread": (q3 - q1) / med if med else None}


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    p = subprocess.run(
        [sys.executable, str(RUN), "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    if p.returncode != 0 or not lines:
        return {"seed": seed, "rc": p.returncode, "err": p.stderr[-2000:]}
    out = json.loads(lines[-1])
    out.update(seed=seed, rc=p.returncode)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sets = []
    for s in range(args.sets):
        runs = []
        for seed in args.seeds:
            r = one_run(args.workload, seed, args.seconds, args.trace)
            runs.append(r)
            brief = {k: v["value"] for k, v in r.get("metrics", {}).items()}
            print(json.dumps({"set": s, "seed": seed, "rc": r["rc"],
                              "correct": r.get("correct"),
                              "metrics": brief}), flush=True)
        sets.append(runs)
    summary = []
    for runs in sets:
        ok = [r for r in runs if r["rc"] == 0]
        names = sorted({k for r in ok for k in r["metrics"]})
        summary.append({
            "runs": len(runs), "ok": len(ok),
            "correct": sum(bool(r.get("correct")) for r in ok),
            "metrics": {k: spread([r["metrics"][k]["value"] for r in ok])
                        for k in names if len(ok) >= 2}})
    print(json.dumps({"workload": args.workload, "sets": summary}),
          flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "runs": sets,
                       "sets": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
