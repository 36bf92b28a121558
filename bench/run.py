#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload gcn-ogbn-arxiv.fwd-f32 --seed 7 \\
        --seconds 10 --trace 0

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  Both
are found by name: ``bench/configs/<config>.json`` (sizes, and the model
whose forward and plain reference live in ``bench/models/<model>.py``),
``bench/mixes/<traffic>.json`` (what the window drives) and
``bench/checks/<cell>.json`` (the limits of the correctness check).  Each
per-layer metric is read by ``bench/metrics/<metric>.py``.

Set-up builds the graph, features and weights from ``--seed``, builds the
program's sampled aggregation once, and runs the warm-up forwards that
compile every program the window uses.  The window then runs back-to-back
full-graph forwards, each ending in ``block_until_ready`` on its logits,
until ``--seconds`` have passed.  After the window the last forward's
aggregations, hidden activation and logits are compared with the plain
reference on the host.  ``--trace 1`` records the window with the JAX
profiler and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
# libtpu would otherwise log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _p in (str(CHECKOUT / "src"), str(CHECKOUT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import graphs, reference, tracing  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

CHECKED = ("agg1", "hidden", "agg2", "logits")


class NoChip(RuntimeError):
    pass


# -- finding things by name ---------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import the file at ``path`` under a name of its own."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def spec_of(workload: str) -> dict:
    """Everything a cell needs, found from its name in BENCHMARK.json."""
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(CHECKOUT / configs[cell["config"]]["file"])
    mix = load_json(BENCH / "mixes" / f"{cell['traffic']}.json")
    checks_file = BENCH / "checks" / f"{workload}.json"
    limits = load_json(checks_file)["limits"] if checks_file.exists() \
        else {}
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}


# -- inputs ---------------------------------------------------------------------

def jax_key(seed: int):
    """A JAX key for any whole ``seed``, 64-bit ones included."""
    import jax

    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(a)), int(b) & 0x7FFFFFFF)


def make_inputs(key, shapes: dict, n: int, g: dict):
    """Features and weights on the device, in one jitted call.

    Features: class means plus noise of scale ``feat_noise``, node ``i`` in
    class ``i * C // n``.  Weights: normal over the square root of the
    fan-in; biases normal at 0.1.
    """
    import jax
    import jax.numpy as jnp

    f, c = g["feat_dim"], g["classes"]

    def build(key):
        kx, km, *kw = jax.random.split(key, 2 + len(shapes))
        comm = (jnp.arange(n, dtype=jnp.int32) * c) // n
        means = jax.random.normal(km, (c, f), jnp.float32)
        x = means[comm] + g["feat_noise"] * jax.random.normal(
            kx, (n, f), jnp.float32)
        params = {}
        for k, (name, shape) in zip(kw, shapes.items()):
            w = jax.random.normal(k, shape, jnp.float32)
            params[name] = w / math.sqrt(shape[0]) if len(shape) == 2 \
                else 0.1 * w
        return x, params

    return jax.jit(build)(key)


def weight_shapes(model, config: dict) -> dict:
    dims = {"F": config["graph"]["feat_dim"], "H": config["hidden"],
            "C": config["graph"]["classes"]}
    return {k: tuple(dims[d] for d in v) for k, v in model.SHAPES.items()}


# -- the check ------------------------------------------------------------------

def reference_outputs(config: dict, mix: dict, graph, x, params, *,
                      precision: str = "highest", bits=None) -> dict:
    """The plain reference's aggregations, hidden activation and logits.

    ``bits`` overrides the mix's quantization width (the control runs the
    int8 mix at 4 bits); ``precision`` is ``"highest"`` or the control's
    ``"high"``."""
    model = load_module(BENCH / "models" / f"{config['model']}.py")
    ar = reference.Arith(precision)
    ell_val, ell_col = reference.aes_sample(
        graph.row_ptr, graph.col_ind, graph.val, config["sh_width"])
    bits = mix.get("quantize_bits") if bits is None else bits
    x = np.asarray(x, np.float32)
    stored = None
    if bits is not None:
        stored = reference.quantize(x, bits)
        x = stored.dequantize()

    def aggregate(h, layer):
        if stored is not None:
            h = reference.requant_guard(stored, np.asarray(h, np.float32))
        return ar.aggregate(ell_val, ell_col, h)

    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    out = model.reference(p, x, aggregate, ar)
    out["input"], out["stored"] = x, stored
    return out


def compare(got: dict, want: dict, limits: dict) -> dict:
    """Per output: the largest absolute gap to the reference over the
    reference's largest magnitude, beside its limit."""
    out = {}
    for name in CHECKED:
        g = np.asarray(got[name], np.float64)
        w = np.asarray(want[name], np.float64)
        if g.shape != w.shape or not np.all(np.isfinite(g)):
            err = math.inf
        else:
            err = float(np.max(np.abs(g - w))
                        / max(float(np.max(np.abs(w))), 1e-30))
        out[name] = {"value": err, "limit": limits.get(name)}
    return out


def input_diagnostics(got_input, want: dict, x) -> dict:
    """How the first aggregation's operand departs from the reference's.

    Under quantization, an element whose gap passes half a step sits on
    another level (a flip): its gap in steps, and how far the exact Eq. 1
    value of the element lies from the rounding boundary between two
    levels, in levels, tell a rounding flip (one step, at the boundary)
    from a fault."""
    gap = np.abs(np.asarray(got_input, np.float64) - want["input"])
    out = {"input_elements_off": int(np.count_nonzero(gap)),
           "input_max_gap": float(gap.max())}
    stored = want["stored"]
    if stored is not None:
        scale = float(stored.scale)
        flip = gap > scale / 2
        span = float(stored.x_max) - float(stored.x_min)
        exact = (np.asarray(x, np.float64) - float(stored.x_min)) / span \
            * (2 ** stored.bits - 1)
        boundary = np.abs(exact - np.floor(exact) - 0.5)
        out.update(
            input_level_flips=int(np.count_nonzero(flip)),
            input_flip_steps_max=float(gap.max() / scale),
            input_flip_boundary_max=float(boundary[flip].max())
            if flip.any() else 0.0,
            input_other_gap_max=float(np.where(flip, 0.0, gap).max()))
    return out


def passed(checks: dict) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


# -- one run --------------------------------------------------------------------

def find_devices(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


class CompileCounter:
    """Counts the compilations JAX reports while ``active``."""

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def build(config: dict, mix: dict, seed: int):
    """Set-up: the graph on the host, then the device arrays and the
    program's sampled aggregation."""
    import jax.numpy as jnp

    from repro.core.graph import CSR
    from repro.core.quantization import dequantize, quantize
    from repro.gnn.models import make_sampled_agg

    model = load_module(BENCH / "models" / f"{config['model']}.py")
    g = config["graph"]
    graph = graphs.make_graph(g, seed, model.ADJACENCY)
    n = graph.num_nodes
    adj = CSR(jnp.asarray(graph.row_ptr), jnp.asarray(graph.col_ind),
              jnp.asarray(graph.val), num_cols=n)
    x, params = make_inputs(jax_key(seed), weight_shapes(model, config), n,
                            g)
    feats, qf = x, None
    if mix.get("quantize_bits") is not None:
        # offline quantization, once (paper §3.3); the forward serves the
        # reconstruction, which the first aggregation re-encodes bit-exactly
        qf = quantize(x, mix["quantize_bits"])
        feats = dequantize(qf)
    agg = make_sampled_agg(config["sh_width"], config["strategy"], "pallas",
                           qf)
    return model, graph, adj, x, feats, params, agg


def work_counts(config: dict, mix: dict, graph, model) -> dict:
    g = config["graph"]
    n, f, h, c = graph.num_nodes, g["feat_dim"], config["hidden"], \
        g["classes"]
    e = graphs.sampled_edges(graph.row_nnz, config["sh_width"])
    item = 1 if mix.get("quantize_bits") else 4
    weights = sum(math.prod(s) for s in weight_shapes(model, config)
                  .values())
    return {
        "nodes": n, "feat": f, "hidden": h, "classes": c,
        "sampled_edges": e, "itemsize": item,
        "fwd_flops": model.flops(n, f, h, c, e),
        "fwd_bytes": graphs.forward_bytes(n, f, c, e, weights, item),
        "spmm_flops": graphs.spmm_flops(f, e) + graphs.spmm_flops(h, e),
        "spmm_bytes": graphs.spmm_bytes(n, f, e, item)
        + graphs.spmm_bytes(n, h, e, item),
    }


class Reading:
    """What a per-layer metric reads: the reduced trace, the forwards in
    it, the work counts and the chip's peaks."""

    def __init__(self, reduced, forwards: int, work: dict, peak: dict):
        self.trace, self.forwards = reduced, forwards
        self.work, self.peak = work, peak
        self._values = {}

    def metric(self, name: str):
        if name not in self._values:
            mod = load_module(BENCH / "metrics" / f"{name}.py")
            self._values[name] = mod.read(self)
        return self._values[name]


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, fault=None, control: bool = False,
             is_op_line=tracing.tpu_ops, peak=None,
             log=print) -> dict:
    """One run of a cell; returns the result object.

    ``fault(stage, array)`` may alter what the timed path produces
    (``agg1``, ``agg2``, ``logits``): the tests break the path with it.
    ``control`` also compares the mix's control, the reference at the
    lower precision its ``control`` entry names, and reports its numbers
    under ``"control"``.  ``is_op_line`` and ``peak`` stand in for the
    TPU's trace lines and peaks in a rehearsal on the CPU."""
    import jax

    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    devices = find_devices(cell["chips"], require_chip)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    compiles = CompileCounter()
    model, graph, adj, x, feats, params, agg = build(config, mix, seed)
    fault = fault or (lambda stage, a: a)
    last = {}

    def agg_spy(csr, h):
        layer = len(last["calls"]) + 1
        with jax.profiler.TraceAnnotation(f"bench.agg{layer}"):
            out = fault(f"agg{layer}", agg(csr, h))
        last["calls"].append((h, out))
        return out

    def forward():
        with jax.profiler.TraceAnnotation("bench.forward"):
            last["calls"] = []
            logits = fault("logits", model.program(params, adj, feats,
                                                    agg_spy))
            last["logits"] = logits.block_until_ready()

    for _ in range(mix["warmup_forwards"]):
        forward()
    setup_s = time.perf_counter() - T_START

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(log_dir)
    compiles.active = True
    forwards = 0
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        t0 = time.perf_counter()
        while True:
            forward()
            forwards += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    compiles.active = False
    if trace:
        jax.profiler.stop_trace()
    stats = devices[0].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")

    (h1, a1), (hidden, a2) = last["calls"]
    got = {"input": np.asarray(h1), "agg1": np.asarray(a1),
           "hidden": np.asarray(hidden),
           "agg2": np.asarray(a2), "logits": np.asarray(last["logits"])}
    x_host = np.asarray(x)
    p_host = {k: np.asarray(v) for k, v in params.items()}
    # the program's state goes before the reference runs
    del adj, x, feats, params, agg, last, h1, a1, hidden, a2
    gc.collect()
    t_ref = time.perf_counter()
    want = reference_outputs(config, mix, graph, x_host, p_host)
    checks = compare(got, want, spec["limits"])
    ok = passed(checks)
    ctl = None
    if control:
        ctl = compare(reference_outputs(config, mix, graph, x_host, p_host,
                                        **mix["control"]),
                      want, spec["limits"])
        diag = input_diagnostics(got["input"], want, x_host)
    log(f"reference and check: {time.perf_counter() - t_ref:.3f} s; "
        f"compiles in the window: {compiles.count}", file=sys.stderr)

    result = {
        "correct": ok, "attempted": forwards,
        "failed": 0 if ok else forwards,
        "metrics": {},
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": peak_bytes},
    }
    if trace:
        try:
            reduced = tracing.reduce(tracing.load(log_dir, is_op_line))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        reading = Reading(reduced, forwards,
                          work_counts(config, mix, graph, model),
                          peak or graphs.peaks(devices[0].device_kind))
        for m in spec["per_layer"]:
            value = reading.metric(m["name"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.top_idle()}
    else:
        values = {"forward_ms": 1e3 * elapsed / forwards, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
            file=sys.stderr)
    if ctl is not None:
        result["control"], result["diagnostics"] = ctl, diag
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = spec_of(args.workload)
    try:
        find_devices(spec["cell"]["chips"], True)
        enable_compile_cache()
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
