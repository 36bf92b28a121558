"""The sampler's roofline share (``bench/metrics/sample_roofline.py``):
its least bytes by hand, and its reading of a trace."""
from __future__ import annotations

import pytest

from bench import run, tracing
from bench.tracing import Event

METRIC = run.load_module(run.BENCH / "metrics" / "sample_roofline.py")
PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
PALLAS = ', custom_call_target="tpu_custom_call"'


def test_sample_bytes_by_hand():
    # 4 rows, 12 live slots at W = 4: row_ptr 5 * 4 = 20 bytes, the live
    # slots' value and column 12 * 8 = 96, the [4, 4] ELL value and
    # column 4 * 4 * 8 = 128
    assert METRIC.sample_bytes(4, 12, 4) == 20 + 96 + 128
    # Reddit at W = 128: 232,965 rows, 27,778,417 sampled edges
    assert METRIC.sample_bytes(232_965, 27_778_417, 128) \
        == 4 * 232_966 + 8 * 27_778_417 + 8 * 232_965 * 128


def sampler_event(start, seconds, rows=8, width=4):
    return Event(f"%aes_sample.{start} = (f32[{rows},{width}]{{1,0}}, "
                 f"s32[{rows},{width}]{{1,0}}) custom-call(s32[1,1,8] "
                 f"%bitcast.2){PALLAS}", float(start),
                 float(start + seconds))


def reading(events, forwards=2, edges=12):
    spans = [Event(tracing.WINDOW, 0.0, 100.0)]
    reduced = tracing.reduce(tracing.Trace([events], spans, []))
    return run.Reading(reduced, forwards,
                       {"nodes": 4, "sampled_edges": edges}, PEAK)


@pytest.mark.parametrize("slower", [1.0, 4.0])
def test_share_is_the_least_time_over_the_sampler_time(slower):
    """Two forwards of two calls each; a call that moves its least bytes
    at peak bandwidth reads 100%, one ``slower`` times longer 100/slower,
    and another kernel's events are not counted.  The rows are the work
    counts' nodes, not the kernel's padded rows."""
    least = METRIC.sample_bytes(4, 12, 4) / PEAK["hbm_bytes_per_s"]
    events = [sampler_event(10 * k, least * slower) for k in range(4)]
    events.append(Event("%ell_spmm.1 = f32[4,128] custom-call()" + PALLAS,
                        50.0, 60.0))
    r = reading(events)
    assert r.metric("sample_ms") == pytest.approx(2e3 * least * slower)
    assert r.metric("sample_roofline") == pytest.approx(100.0 / slower)


def test_a_trace_without_the_sampler_reads_nothing():
    """As in a rehearsal on the CPU, where interpret mode runs no Mosaic
    kernel: no share, and no error."""
    r = reading([Event("%fusion.1 = f32[4] fusion()", 1.0, 2.0)])
    assert r.metric("sample_roofline") is None


def test_a_sampler_whose_shape_cannot_be_read_reads_nothing():
    r = reading([Event("%aes_sample.1 = custom-call()" + PALLAS, 1.0, 2.0)])
    assert r.metric("sample_ms") is not None
    assert r.metric("sample_roofline") is None

