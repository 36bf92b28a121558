#!/usr/bin/env python3
"""Where the program's forward spends host time, and which of its layers
the chip waits on, from one traced run of a cell.

    python3 bench/program_spans.py --workload graphsage-pubmed.fwd-int8 \\
        --seed 7 --seconds 10

While ``repro.obs`` is on, each of its spans is also a profiler annotation
named ``repro.<span>``, on the device trace's clock: ``repro.sample``,
``repro.quant.requant_guard``, ``repro.exec.run_ell`` and
``repro.gnn.dense`` along the forward.  This runs the cell as
``bench/run.py --trace 1`` does, keeps its trace, and prints one JSON
object, per forward where it says ``_ms``:

* ``host_path_ms``: the union of the ``repro.*`` spans in the window, the
  host time the forward spends in the program's layers, time blocked in
  its own syncs included;
* ``host_idle_ms``: the chip's idle time whose gap midpoint lies in a
  ``repro.*`` span; ``idle_by_span`` splits it by the innermost such span
  (seconds in the window, largest first);
* ``span_ms``: each span name's own time, nested spans counted in each;
* ``outside_forward``: how many of those spans lie outside every
  ``bench.forward`` span (0 when the spans nest as they should);
* ``jit_traces``, ``jit_compiles``: the ``repro.obs`` counts of what JAX
  traced and compiled inside the window.

Both ``host_*_ms`` are null when the window holds no ``repro.*`` span
(obs off, or broken instrumentation): never 0.  Without a chip the run
exits 2, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from bench import run, tracing  # noqa: E402
from repro import obs  # noqa: E402

PREFIX = "repro."
JIT = ("traces", "compiles")


def window(trace) -> tuple:
    (w,) = [s for s in trace.spans if s.name == tracing.WINDOW]
    return w.start, w.end


def program_spans(trace) -> list:
    """The program's spans in the window."""
    lo, hi = window(trace)
    return [e for e in trace.host
            if e.name.startswith(PREFIX) and e.end > lo and e.start < hi]


def program_seconds(trace):
    """Union of the program's spans within the window, or None."""
    spans = program_spans(trace)
    if not spans:
        return None
    return sum(e - s for s, e in tracing.merged(spans, *window(trace)))


def idle_by_span(trace) -> dict:
    """Idle seconds of the chips, mean over chips, by the innermost
    program span at each gap's midpoint; gaps outside every span are
    left out."""
    lo, hi = window(trace)
    spans = program_spans(trace)
    out = defaultdict(float)
    ndev = len(trace.devices)
    for events in trace.devices:
        gaps = tracing.idle(tracing.merged(events, lo, hi), lo, hi)
        for (s, e), span in zip(gaps, tracing.innermost(
                spans, [(s + e) / 2 for s, e in gaps])):
            if span is not None:
                out[span.name] += (e - s) / ndev
    return dict(out)


def read(trace, forwards: int) -> dict:
    """The program-span breakdown of a trace of ``forwards`` forwards."""
    lo, hi = window(trace)
    program = program_seconds(trace)
    idle = idle_by_span(trace)
    per = 1e3 / forwards
    own = defaultdict(float)
    spans = program_spans(trace)
    for e in spans:
        own[e.name] += min(e.end, hi) - max(e.start, lo)
    fwd = [f for f in trace.spans if f.name == "bench.forward"]
    return {
        "host_path_ms": None if program is None else program * per,
        "host_idle_ms": None if program is None
        else sum(idle.values()) * per,
        "idle_by_span": sorted(([k, v] for k, v in idle.items()),
                               key=lambda kv: -kv[1])[:tracing.TOP],
        "span_ms": {k: v * per for k, v in sorted(own.items())},
        "outside_forward": sum(
            not any(f.start <= e.start and e.end <= f.end for f in fwd)
            for e in spans),
    }


def run_traced(spec: dict, seed: int, seconds: float, **kw):
    """One ``--trace 1`` run of the cell through ``run.run_cell``.

    For the length of the run, ``run.py``'s own trace loading keeps the
    trace it loads and its compile counter also takes the window's
    ``jit.*`` counts.  Returns the run's result, the trace and the
    counts."""
    kept, counts = [], {}
    load, counter = tracing.load, run.CompileCounter

    class WindowCounter(counter):
        def __setattr__(self, name, value):
            if name == "active":
                reg = obs.default_registry()
                now = {k: reg.counter_value(f"jit.{k}") for k in JIT}
                if value:
                    counts.update(now)
                elif counts:
                    counts.update({k: now[k] - counts[k] for k in JIT})
            super().__setattr__(name, value)

    def keep(*a, **k):
        kept.append(load(*a, **k))
        return kept[-1]

    tracing.load, run.CompileCounter = keep, WindowCounter
    try:
        result = run.run_cell(spec, seed, seconds, True, **kw)
    finally:
        tracing.load, run.CompileCounter = load, counter
    return result, kept[0], {f"jit_{k}": counts.get(k) for k in JIT}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.spec_of(args.workload)
    try:
        run.find_devices(spec["cell"]["chips"], True)
    except run.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    result, trace, jit = run_traced(spec, args.seed, args.seconds)
    out = {"workload": args.workload, "seed": args.seed,
           "correct": result["correct"], "forwards": result["attempted"],
           **read(trace, result["attempted"]), **jit,
           "metrics": {k: v["value"] for k, v in result["metrics"].items()},
           "idle_gaps": result["breakdown"]["idle_gaps"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
