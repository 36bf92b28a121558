"""The program's ``repro.*`` spans in a benchmark trace: the breakdown on
hand-made events, and on whole tiny runs recorded on the CPU."""
from __future__ import annotations

import pytest

from bench import program_spans as ps
from bench.tests.test_bench_rehearsal import SEED, quiet, tiny
from bench.tracing import Event, Trace


def ev(name, start, end):
    return Event(name, float(start), float(end))


def test_breakdown_by_hand():
    spans = [ev("bench.window", 0, 10), ev("bench.forward", 0, 10)]
    host = [ev("repro.sample", 1, 4), ev("repro.quant.requant_guard", 2, 3),
            ev("repro.gnn.dense", 6, 8), ev("repro.sample", 11, 12),
            ev("PjitFunction(ell_spmm)", 2.2, 2.8)]
    # idle: (0,1) outside every span, (2,3) in the guard, (3.5,4.5) mid 4
    # in the sampler, (6.5,7) in the dense step, (9,10) outside
    device = [ev("op", 1, 2), ev("op", 3, 3.5), ev("op", 4.5, 6.5),
              ev("op", 7, 9)]
    trace = Trace([device], spans, host)
    assert ps.program_seconds(trace) == pytest.approx(5)
    assert ps.idle_by_span(trace) == pytest.approx(
        {"repro.quant.requant_guard": 1, "repro.sample": 1,
         "repro.gnn.dense": 0.5})
    r = ps.read(trace, forwards=2)
    assert r["host_path_ms"] == pytest.approx(2500)
    assert r["host_idle_ms"] == pytest.approx(1250)
    assert r["idle_by_span"][-1] == ["repro.gnn.dense", 0.5]
    assert r["span_ms"] == pytest.approx(
        {"repro.gnn.dense": 1000, "repro.quant.requant_guard": 500,
         "repro.sample": 1500})
    assert r["outside_forward"] == 0
    outside = Trace([device], spans[:1] + [ev("bench.forward", 0, 5)],
                    host)
    assert ps.read(outside, forwards=2)["outside_forward"] == 1


def test_breakdown_without_program_spans_is_null_not_zero():
    trace = Trace([[ev("op", 1, 2)]], [ev("bench.window", 0, 10)],
                  [ev("repro.sample", 11, 12), ev("other", 1, 5)])
    assert ps.program_seconds(trace) is None
    assert ps.idle_by_span(trace) == {}
    r = ps.read(trace, forwards=1)
    assert r["host_path_ms"] is None and r["host_idle_ms"] is None


LAYERS = {"repro.sample": "bench.agg", "repro.exec.run_ell": "bench.agg",
          "repro.quant.requant_guard": "bench.agg",
          "repro.gnn.dense": "bench.forward"}


@pytest.mark.parametrize("cell", ["gcn-ogbn-arxiv.fwd-f32",
                                  "graphsage-pubmed.fwd-int8"])
def test_program_spans_nest_in_the_benchmark_spans(cell):
    """A tiny traced run on the CPU: the forward's layers lie inside the
    benchmark's spans on the same clock, the requant guard in the int8
    cell only, and the breakdown reads them."""
    from repro.compile_cache import count_compile_events

    def cpu_ops(plane, line):
        return plane == "/host:CPU" and line.startswith("tf_XLA")

    count_compile_events()
    result, trace, jit = ps.run_traced(
        tiny(cell), SEED, 0.0, require_chip=False, log=quiet,
        is_op_line=cpu_ops, peak={"flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11})
    assert result["correct"] and result["attempted"] == 1
    found = {e.name for e in ps.program_spans(trace)}
    want = set(LAYERS) - (set() if cell.endswith("int8")
                          else {"repro.quant.requant_guard"})
    assert found == want
    bench = trace.spans
    for e in ps.program_spans(trace):
        outer = [s for s in bench if s.name.startswith(LAYERS[e.name])
                 and s.start <= e.start and e.end <= s.end]
        assert outer, e
        inside_agg = any(s.name.startswith("bench.agg") and
                         s.start <= e.start and e.end <= s.end
                         for s in bench)
        assert inside_agg == (LAYERS[e.name] == "bench.agg"), e
    r = ps.read(trace, result["attempted"])
    assert r["host_path_ms"] > 0 and r["host_idle_ms"] >= 0
    assert set(r["span_ms"]) == want and r["outside_forward"] == 0
    assert jit == {"jit_traces": 0, "jit_compiles": 0}
