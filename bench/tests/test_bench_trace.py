"""The trace reducer, on hand-made events and on a profiler trace
recorded on the CPU in the test itself."""
from __future__ import annotations

import pytest

from bench import tracing
from bench.tracing import Event, Trace


def ev(name, start, end):
    return Event(name, float(start), float(end))


def test_merged_and_idle_by_hand():
    events = [ev("a", 1, 3), ev("b", 2, 4), ev("c", 6, 7), ev("d", 9, 12)]
    busy = tracing.merged(events, 0, 10)
    assert busy == [(1, 4), (6, 7), (9, 10)]
    assert tracing.idle(busy, 0, 10) == [(0, 1), (4, 6), (7, 9)]


def test_reduce_attributes_idle_to_the_innermost_span():
    spans = [ev("bench.window", 0, 10), ev("bench.forward", 0, 5),
             ev("bench.agg1", 0, 2), ev("bench.forward", 5, 10)]
    host = [ev("PjitFunction(ell_spmm)", 0.5, 1.5)]
    spmm = '%ell_spmm.1 = f32[8,128] custom-call(f32[8,128] %x), ' \
        'custom_call_target="tpu_custom_call"'
    device = [ev("%fusion.2 = f32[8] fusion(f32[8] %ell_spmm.1)", 1, 2),
              ev(spmm, 3, 4), ev("fusion", 6, 9)]
    r = tracing.reduce(Trace([device], spans, host))
    assert r.window_s == 10 and r.busy_s == 5 and r.devices == 1
    assert r.op_seconds == {"fusion": 4, "ell_spmm": 1}
    # gaps: (0,1) mid 0.5 in the host event, (2,3) in forward only,
    # (4,6) mid 5: both forwards touch it, the first is as short; (9,10)
    assert r.idle_by_host["bench.agg1: PjitFunction(ell_spmm)"] == 1
    assert sum(r.idle_by_host.values()) == pytest.approx(5)
    assert r.top_ops()[0] == ["fusion", 4]
    assert r.seconds(tracing.is_pallas) == 1


def test_reduce_averages_over_chips_and_clips_to_the_window():
    spans = [ev("bench.window", 0, 10)]
    chips = [[ev("op", -5, 5)], [ev("op", 0, 10), ev("op", 9, 20)]]
    r = tracing.reduce(Trace(chips, spans, []))
    assert r.busy_s == pytest.approx(7.5)
    assert r.op_seconds["op"] == pytest.approx(8.0)


def test_op_names_from_hlo_text():
    kernel = ev('%aes_sample.1 = (f32[8,128], s32[8,128]) custom-call('
                's32[1,1,8] %bitcast.2), custom_call_target="tpu_custom_call"',
                0, 1)
    assert tracing.op_name(kernel) == "aes_sample"
    assert tracing.is_pallas(kernel)
    user = ev("%slice.3 = f32[8,64] slice(f32[8,128] %ell_spmm.1)", 0, 1)
    assert tracing.op_name(user) == "slice" and not tracing.is_pallas(user)
    assert tracing.op_name(ev("copy-done", 0, 1)) == "copy-done"
    assert tracing.op_name(ev("broadcast_add_fusion", 0, 1)) \
        == "broadcast_add_fusion"


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        tracing.reduce(Trace([[ev("op", 0, 1)]], [], []))


def test_a_recorded_cpu_trace(tmp_path):
    """Record a trace of two jitted steps on the CPU; the CPU client's
    thread stands in for a device.  Busy time lies within the window and
    every op seen there is one the steps ran."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x @ x.T).sum(0))
    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.forward"):
                step(x).block_until_ready()
    jax.profiler.stop_trace()

    def cpu_ops(plane, line):
        return plane == "/host:CPU" and line.startswith("tf_XLA")

    t = tracing.load(str(tmp_path), cpu_ops)
    assert [s.name for s in t.spans].count("bench.forward") == 3
    r = tracing.reduce(t)
    assert r.devices == 1
    assert 0 < r.busy_s <= r.window_s
    assert r.ops and all(e.end > 0 for e in r.ops)
    assert sum(r.idle_by_host.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6, abs=1e-9)
    assert any(name.startswith("bench.") for name in r.idle_by_host)
