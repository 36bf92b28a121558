"""Observability layer (``repro.obs``): span tree semantics, metrics
registry, the shared ``LatencyHistogram``, and the quality counters the
instrumented subsystems emit."""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.obs.metrics import LatencyHistogram, MetricsRegistry

from conftest import random_csr


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test runs enabled against empty state, and leaves the
    process-wide singletons the way it found them."""
    prev = obs.set_enabled(True)
    obs.reset()
    yield
    obs.reset()
    obs.set_enabled(prev)


# ---------------------------------------------------------------- spans

def test_trace_nesting_and_context_propagation():
    with obs.trace("outer", k=1) as sp_out:
        assert obs.current_context() == (sp_out.trace_id, sp_out.span_id)
        with obs.trace("inner") as sp_in:
            assert sp_in.trace_id == sp_out.trace_id
            assert sp_in.parent_id == sp_out.span_id
    assert obs.current_context() is None
    spans = obs.default_tracer().spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # exit order
    assert all(s.t1 >= s.t0 and s.status == "ok" for s in spans)


def test_trace_error_status_propagates_exception():
    with pytest.raises(ValueError):
        with obs.trace("boom"):
            raise ValueError("nope")
    (sp,) = obs.default_tracer().spans()
    assert sp.status == "error" and sp.attrs["error"] == "ValueError"


def test_traced_decorator_and_record_span():
    @obs.traced("named.fn", tag="x")
    def f(a, b):
        return a + b

    assert f(2, 3) == 5
    (sp,) = obs.default_tracer().spans()
    assert sp.name == "named.fn" and sp.attrs["tag"] == "x"
    child = obs.record_span("retro", sp.t0, sp.t1, trace_id=sp.trace_id,
                            parent_id=sp.span_id, rows=7)
    assert child.trace_id == sp.trace_id and child.attrs["rows"] == 7
    trees = obs.build_trees(obs.default_tracer().spans())
    (roots,) = trees.values()
    assert roots[0]["children"][0]["record"]["name"] == "retro"
    assert obs.validate_tree(obs.default_tracer().spans())["well_formed"]


def test_disabled_mode_is_inert():
    obs.set_enabled(False)
    with obs.trace("ghost") as sp:
        sp.set(x=1)  # no-op span accepts the API
        obs.count("ghost.counter")
        obs.gauge("ghost.gauge", 3)
        obs.observe_us("ghost.hist", 10.0)
        with obs.decision("ghost"):
            pass
    assert obs.default_tracer().recorded == 0
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert obs.request_context()[1] is None  # still mints fresh trace ids


def test_ring_buffer_bounded_and_lifetime_counter():
    cap = obs.default_tracer().capacity
    for i in range(cap + 32):
        with obs.trace("s", i=i):
            pass
    tr = obs.default_tracer()
    assert len(tr.spans()) == cap
    assert tr.recorded == cap + 32


def test_jsonl_sink_and_perfetto_export(tmp_path):
    obs.configure(sink_dir=str(tmp_path))
    try:
        with obs.trace("parent"):
            with obs.trace("child", n=2):
                pass
        assert obs.default_tracer().flush() == 2
        records = obs.load_trace_dir(str(tmp_path))
        assert {r["name"] for r in records} == {"parent", "child"}

        out = tmp_path / "perfetto.json"
        assert obs.write_perfetto(str(out), records) == 2
        doc = json.loads(out.read_text())
        assert doc["displayTimeUnit"] == "ms"
        for ev in doc["traceEvents"]:
            assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["cat"] == "repro"
    finally:
        obs.configure(sink_dir=None)


def test_decision_spans_parent_under_current_context():
    with obs.trace("tuneish") as sp:
        obs.decision("tuneish", choice="aes")
    spans = obs.default_tracer().spans()
    dec = next(s for s in spans if s.name == "tuneish.decision")
    assert dec.parent_id == sp.span_id and dec.attrs["choice"] == "aes"
    assert obs.snapshot()["counters"]["tuneish.decisions"] == 1


@pytest.mark.parametrize("form", ["with", "decorator"])
def test_spans_annotate_the_profiler_only_while_enabled(monkeypatch, form):
    import importlib

    import jax

    trace_mod = importlib.import_module("repro.obs.trace")
    assert trace_mod._annotation() is jax.profiler.TraceAnnotation
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            opened.append("/" + self.name)

    monkeypatch.setattr(trace_mod, "_annotation_cls", Annotation)

    def run(name):
        if form == "with":
            with obs.trace(name):
                with obs.trace("inner"):
                    pass
        else:
            obs.traced(name)(obs.traced("inner")(lambda: None))()

    run("outer")
    assert opened == ["repro.outer", "repro.inner", "/repro.inner",
                      "/repro.outer"]
    obs.set_enabled(False)
    run("ghost")
    assert len(opened) == 4


# -------------------------------------------------------------- metrics

def test_metrics_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.count("a.b")
    reg.count("a.b", 4)
    reg.count("a.c")
    reg.gauge("depth", 3)
    reg.gauge("depth", 1)
    reg.observe_us("lat", 100.0)
    assert reg.counter_value("a.b") == 5
    assert reg.counters("a.") == {"a.b": 5, "a.c": 1}
    assert reg.gauge_value("depth") == 1
    snap = reg.snapshot()
    assert snap["histograms"]["lat"]["count"] == 1
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_deferred_counts_fold_on_the_device_and_read_with_the_counters():
    import jax.numpy as jnp

    from repro.obs import metrics

    reg = MetricsRegistry()
    reg.count("c", 1)
    for v in (3, 4, 5):
        reg.count_deferred(("c",), jnp.array([v], jnp.int32), bound=10)
    assert reg.counter_value("c") == 13
    assert reg.counters() == {"c": 13}
    # a sum that could pass int32 is sealed; every sealed sum is read
    big = metrics.DEVICE_SUM_MAX // 2 + 1
    for _ in range(5):
        reg.count_deferred(("c",), jnp.array([big], jnp.int32), bound=big)
    assert reg.snapshot()["counters"]["c"] == 13 + 5 * big
    reg.reset()
    reg.count_deferred(("c", "d"), jnp.array([1, 2], jnp.int32), bound=2)
    reg.reset(["c"])
    assert reg.counters() == {"d": 2}


def test_deferred_counts_of_a_tuple_fold_as_one_vector():
    """A vector counted into a tuple of counters folds with one device
    add a call, seals as a whole, and reads into each counter."""
    import jax.numpy as jnp

    from repro.obs import metrics

    reg = MetricsRegistry()
    names = ("a", "b", "c")
    for v in (1, 2):
        reg.count_deferred(names, jnp.array([v, 10 * v, 0], jnp.int32),
                           bound=20)
    assert reg.counters() == {"a": 3, "b": 30, "c": 0}
    big = metrics.DEVICE_SUM_MAX // 2 + 1
    for _ in range(3):
        reg.count_deferred(names, jnp.array([big, 1, 0], jnp.int32),
                           bound=big)
    assert reg.snapshot()["counters"] == {"a": 3 + 3 * big, "b": 33,
                                          "c": 0}


def test_a_deferred_add_may_count_into_its_own_registry():
    """The first device add of a dtype traces, and JAX's trace listener
    counts into the registry the add folds into, on the same thread."""
    reg = MetricsRegistry()

    class Vector(list):
        def __add__(self, other):
            reg.count("jit.traces")
            return Vector(a + b for a, b in zip(self, other))

        def tolist(self):
            return list(self)

    done = threading.Event()

    def add():
        for _ in range(2):
            reg.count_deferred(("c",), Vector([7]), bound=7)
        done.set()

    threading.Thread(target=add, daemon=True).start()
    assert done.wait(30), "count_deferred deadlocked"
    assert reg.counters() == {"c": 14, "jit.traces": 1}


def test_latency_histogram_clamps_overflow_and_underflow():
    h = LatencyHistogram()
    h.record(0.0)          # underflow -> bucket 0
    h.record(-5.0)         # ignored (invalid)
    h.record(float("nan"))  # ignored
    h.record(0.5)          # below 1us lower bound -> clamped
    h.record(1e12)         # overflow -> clamped into last bucket
    assert h.count == 3
    assert h.percentile(0) >= 0.0
    # the overflow sample lands in the last bucket: the percentile
    # estimate tops out at the histogram range while max_us is exact
    assert h.percentile(100) == pytest.approx(h.hi_us)
    assert h.max_us == 1e12
    assert h.min_us == 0.0
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["max_us"] == 1e12
    h.reset()
    assert h.count == 0 and h.percentile(50) == 0.0


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=1e9,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=200))
def test_latency_histogram_percentiles_monotone(samples):
    h = LatencyHistogram()
    for s in samples:
        h.record(s)
    p50, p95, p99 = h.percentile(50), h.percentile(95), h.percentile(99)
    assert 0.0 <= p50 <= p95 <= p99 <= h.max_us
    tol = 1e-6 * max(1.0, h.max_us)
    assert h.min_us - tol <= h.mean_us <= h.max_us + tol


def test_latency_histogram_concurrent_record():
    h = LatencyHistogram()
    n_threads, per_thread = 8, 2000

    def worker(seed):
        rng = np.random.default_rng(seed)
        for us in rng.uniform(1.0, 1e6, per_thread):
            h.record(float(us))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n_threads * per_thread
    snap = h.snapshot()
    assert snap["count"] == h.count
    assert snap["p50_us"] <= snap["p95_us"] <= snap["p99_us"]


def test_latency_histogram_reexported_from_telemetry():
    from repro.serving.telemetry import LatencyHistogram as TelemetryHist

    assert TelemetryHist is LatencyHistogram


# --------------------------------------------- subsystem quality counters

def test_sampler_counters_account_for_all_edges(rng):
    from repro.core.aes_spmm import sample

    csr = random_csr(rng, 64, 8.0, skew=0.8)
    sample(csr, 4, "aes")  # W below max degree -> must drop
    c = obs.snapshot()["counters"]
    assert c["sampler.calls"] == 1 and c["sampler.calls.aes"] == 1
    assert c["sampler.edges_dropped"] > 0
    assert c["sampler.edges_kept"] + c["sampler.edges_dropped"] == csr.nnz


@pytest.fixture
def host_reads(monkeypatch):
    """The shapes of the device arrays read on the host (every
    ``np.asarray``/``int``/``float``/``bool`` of one goes through
    ``_value``), while ``reads.on``; the CPU backend ignores
    ``jax.transfer_guard``."""
    from jax._src.array import ArrayImpl

    value = ArrayImpl._value

    class Reads(list):
        on = True

    reads = Reads()

    def counted(self):
        if reads.on:
            reads.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(counted))
    return reads


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_sampling_makes_no_host_read(rng, host_reads, backend):
    from repro.core.aes_spmm import sample

    csr = random_csr(rng, 64, 8.0, skew=0.8)
    host_reads.on = True
    sample(csr, 4, "aes", backend)
    sample(csr, 4, "aes", backend)
    assert host_reads == []
    c = obs.snapshot()["counters"]
    assert c["sampler.edges_kept"] + c["sampler.edges_dropped"] \
        == 2 * csr.nnz
    assert {s.name for s in obs.default_tracer().spans()} == {"sample"}


def test_sampler_row_counters_count_each_path(host_reads):
    """On the pallas backend the sampler counts its rows by kernel path,
    on the device: copied whole (nnz <= W), sampled (nnz > W), and copied
    whole with a DMA of their own (after a hub longer than the block's
    staged window, here 2,048 entries).  Counting reads nothing."""
    import jax.numpy as jnp

    from repro.core.aes_spmm import sample
    from repro.core.graph import CSR

    deg = np.array([3, 0, 4, 3000, 2, 0, 5, 1, 4, 9] + [1] * 10)
    row_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    nnz = int(row_ptr[-1])
    csr = CSR(jnp.asarray(row_ptr), jnp.zeros(nnz, jnp.int32),
              jnp.ones(nnz, jnp.float32), 20)
    host_reads.on = True
    sample(csr, 4, "aes", "pallas")
    sample(csr, 4, "aes", "jax")
    assert host_reads == []
    host_reads.on = False
    c = obs.snapshot()["counters"]
    assert c["sampler.rows_sampled"] == 3      # 3000, 5, 9
    assert c["sampler.rows_whole"] == 17       # the empty rows too
    assert c["sampler.rows_own_dma"] == 13     # 2, 1, 4 and ten 1s
    assert c["sampler.calls"] == 2


def test_sampler_counts_run_dmas_and_rows_per_band(host_reads):
    """On the pallas backend the sampler also counts, on the device, the
    run DMAs of the rows over W (one per sample) and those rows per
    Table-1 band, named by samples per row; on a skewed graph with rows
    in every band they equal numpy's count over ``row_nnz``.  Counting
    reads nothing."""
    import jax.numpy as jnp

    from repro.core.aes_spmm import sample
    from repro.core.graph import CSR

    w, n = 8, 2000
    tail = np.random.default_rng(0).pareto(1.6, n) + 0.25
    deg = np.clip(np.round(tail * 60 / tail.mean()), 1, n - 1).astype(int)
    row_ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    nnz = int(row_ptr[-1])
    csr = CSR(jnp.asarray(row_ptr), jnp.zeros(nnz, jnp.int32),
              jnp.ones(nnz, jnp.float32), n)
    host_reads.on = True
    sample(csr, w, "aes", "pallas")
    assert host_reads == []
    host_reads.on = False
    c = obs.snapshot()["counters"]
    over = deg > w
    bounds = [w, 2 * w, 36 * w, 54 * w, n]     # R = 1, 2, 36, 54
    want = {}
    for (lo, hi), samples in zip(zip(bounds, bounds[1:]), (4, 8, 16, 32)):
        rows = int(((deg > lo) & (deg <= hi)).sum())
        assert rows > 0
        want[f"sampler.rows_band{samples}"] = rows
        want["sampler.sample_runs"] = want.get("sampler.sample_runs", 0) \
            + rows * min(samples, w)
    assert {k: c[k] for k in want} == want
    assert c["sampler.rows_sampled"] == over.sum()


def test_spmm_counts_which_gather_each_call_takes(rng, host_reads):
    """``ops.ell_spmm`` counts its gather on the host from the operand's
    shape: a row of F = 602 spans 5 lane tiles and takes the DMA ring, one
    of F = 128 the two-slot loop.  Counting reads nothing."""
    import jax.numpy as jnp

    from repro.core.aes_spmm import sample
    from repro.exec.executor import PlanExecutor

    csr = random_csr(rng, 24, 4.0, skew=0.8)
    ell = sample(csr, 4, "aes", "jax")
    x = {f: jnp.asarray(rng.normal(size=(24, f)).astype(np.float32))
         for f in (602, 128)}
    host_reads.on = True
    for f in (602, 128):
        PlanExecutor().run_ell(ell, x[f], backend="pallas")
    assert host_reads == []
    host_reads.on = False
    c = obs.snapshot()["counters"]
    assert (c["spmm.ring_calls"], c["spmm.ring_tiles"],
            c["spmm.tile_calls"]) == (1, 5, 1)


@pytest.mark.parametrize("model,bits", [("gcn", None), ("graphsage", 8)],
                         ids=["gcn-f32", "graphsage-int8"])
def test_forward_reads_the_host_only_in_the_requant_guard(
        rng, host_reads, monkeypatch, model, bits):
    import jax.numpy as jnp

    from repro.core.quantization import quantize
    from repro.exec import executor
    from repro.gnn.models import MODELS, make_sampled_agg

    csr = random_csr(rng, 64, 8.0, skew=0.8)
    x = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    qf = None if bits is None else quantize(x, bits)
    init, forward, _ = MODELS[model]
    params = init(np.random.default_rng(0), 16, 8, 3)
    guard = executor._guarded_requant

    def guarded(*a, **k):
        host_reads.on = False
        try:
            return guard(*a, **k)
        finally:
            host_reads.on = True

    monkeypatch.setattr(executor, "_guarded_requant", guarded)
    forward(params, csr, x, make_sampled_agg(4, "aes", "pallas", qf))
    assert host_reads == []
    names = [s.name for s in obs.default_tracer().spans()]
    assert names.count("sample") == names.count("exec.run_ell") == 2
    assert names.count("gnn.dense") == 2
    assert names.count("quant.requant_guard") == (0 if bits is None else 2)


def test_jit_traces_count_one_per_new_shape():
    import jax
    import jax.numpy as jnp

    from repro.compile_cache import count_compile_events

    count_compile_events()
    count_compile_events()      # a second call adds no second listener
    a, b = jnp.ones((3,)), jnp.ones((5, 2))
    # lax primitives only: a jitted callee would be a jaxpr of its own
    f = jax.jit(lambda x: jax.lax.sin(x))
    before = obs.default_registry().counter_value("jit.traces")
    f(a)
    f(b)
    f(b)
    assert obs.default_registry().counter_value("jit.traces") \
        == before + 2


def test_plan_cache_counters_and_spans(rng):
    import jax.numpy as jnp

    from repro.tuning.autotune import tune
    from repro.tuning.cost_model import CandidateConfig
    from repro.tuning.plan_cache import PlanCache

    csr = random_csr(rng, 48, 5.0)
    feats = jnp.asarray(np.random.default_rng(0).normal(
        size=(48, 8)).astype(np.float32))
    cache = PlanCache()
    kw = dict(grid=[CandidateConfig("aes", 4, "jax")], budget=1,
              warmup=0, iters=1)
    tune(csr, feats, cache=cache, **kw)   # miss + put
    tune(csr, feats, cache=cache, **kw)   # memory hit
    c = obs.snapshot()["counters"]
    assert c["plan_cache.miss"] >= 1
    assert c["plan_cache.hit_memory"] >= 1
    assert c["plan_cache.put"] >= 1
    assert c["tune.decisions"] == 1       # second call short-circuits
    spans = obs.default_tracer().spans()
    get_sp = next(s for s in spans if s.name == "plan_cache.get"
                  and s.attrs.get("tier") == "memory")
    tune_traces = {s.trace_id for s in spans if s.name == "tune"}
    assert get_sp.trace_id in tune_traces  # hit nested under a tune call
    assert any(k.startswith("executor.") for k in c)  # tuner measured


def test_telemetry_failed_requests_record_stage_latencies():
    from repro.serving.runtime import RuntimeRequest
    from repro.serving.telemetry import Telemetry

    tel = Telemetry()
    r = RuntimeRequest(None, 0.0)
    r.t_flush = 0.010
    r.t_complete = 0.025
    tel.record_request(r, failed=True)
    assert tel.counters["failed"] == 1 and tel.counters["completed"] == 0
    snap = tel.snapshot()
    assert snap["latency"]["queue"]["count"] == 1
    assert snap["latency"]["device"]["count"] == 1
    assert snap["latency"]["total"]["count"] == 1


def test_runtime_queue_depth_gauge_decays_to_zero(rng):
    import jax.numpy as jnp

    from repro.serving.engine import GNNServer
    from repro.serving.runtime import ServingRuntime

    csr = random_csr(rng, 48, 5.0)
    feats = jnp.asarray(np.random.default_rng(1).normal(
        size=(48, 8)).astype(np.float32))
    w = max(int(np.asarray(csr.row_nnz()).max()), 1)
    server = GNNServer(csr, feats, num_shards=2,
                       tune_kwargs=dict(widths=(w,), include_full=True,
                                        measure_plan=False, warmup=0,
                                        iters=1))
    with ServingRuntime(server, max_batch=4, max_delay_ms=5.0) as rt:
        reqs = [rt.submit() for _ in range(5)]
        for r in reqs:
            r.result(60)
        snap = rt.snapshot()
    assert snap["counters"]["queue_depth"] == 0
    assert snap["counters"]["queue_peak"] >= 1
    roots = [s for s in obs.default_tracer().spans()
             if s.name == "serve.request"]
    assert len(roots) == 5
    assert {s.status for s in roots} == {"ok"}
