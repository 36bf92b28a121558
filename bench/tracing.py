"""From a profiler trace to per-layer times.

The benchmark records one JAX profiler trace of its measured window and
reduces it here:

* device events: the operations that ran on each chip (a line of each
  device plane), named by their HLO text;
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
  ``bench.*``) and the other host events, on the same clock;
* busy time: the union of the device events within the ``bench.window``
  span, averaged over the chips;
* idle gaps: the stretches of that window in which a chip ran nothing,
  attributed to what the host was doing at their midpoint.

Nothing here knows a kernel's name: the per-layer metrics classify events
in their own files, by :func:`op_name` and :func:`is_pallas`.
"""
from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
TOP = 10


@dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds on the trace's clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def op_name(ev: Event) -> str:
    """An op event's short name: a TPU trace names each op by its HLO
    text, ``%ell_spmm.1 = f32[...] custom-call(...)``; this keeps the
    instruction's name without the ``%`` and the numeric suffix
    (``ell_spmm``)."""
    name = ev.name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def is_pallas(ev: Event) -> bool:
    """Whether the op is a Mosaic (Pallas TPU) kernel."""
    return 'custom_call_target="tpu_custom_call"' in ev.name


def tpu_ops(plane: str, line: str) -> bool:
    """The op line of a TPU device plane."""
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


@dataclass
class Trace:
    devices: list            # one list of Events per device
    spans: list              # the benchmark's host spans
    host: list               # every other host event


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return found[-1]


def _event(e) -> Event:
    return Event(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)


def load(log_dir: str,
         is_op_line: Callable[[str, str], bool] = tpu_ops) -> Trace:
    """Read the trace under ``log_dir``.  ``is_op_line(plane, line)``
    picks the lines whose events are device operations, one device per
    plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_file(log_dir))
    devices, spans, host = {}, [], []
    for plane in data.planes:
        for line in plane.lines:
            if is_op_line(plane.name, line.name):
                devices.setdefault(plane.name, []).extend(
                    _event(e) for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    (spans if e.name.startswith(SPAN_PREFIX)
                     else host).append(_event(e))
    return Trace([sorted(v, key=lambda e: e.start)
                  for _, v in sorted(devices.items())], spans, host)


def merged(events, lo: float, hi: float) -> list:
    """The union of the events' intervals within ``[lo, hi]``, as sorted
    disjoint ``(start, end)`` pairs."""
    out = []
    for s, e in sorted((max(ev.start, lo), min(ev.end, hi))
                       for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def idle(busy: list, lo: float, hi: float) -> list:
    """The complement of ``busy`` (sorted, disjoint) within ``[lo, hi]``."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def innermost(events, times) -> list:
    """For each of the sorted ``times``, the shortest event that covers
    it, or None: one sweep, with a heap of the events begun so far keyed
    by length."""
    order = sorted(events, key=lambda e: e.start)
    heap, out, i = [], [], 0
    for t in times:
        while i < len(order) and order[i].start <= t:
            heapq.heappush(heap, (order[i].seconds, i, order[i]))
            i += 1
        while heap and heap[0][2].end < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else None)
    return out


@dataclass
class Reduced:
    window_s: float                 # length of the bench.window span
    busy_s: float                   # device busy time, mean over chips
    ops: list                       # device events in the window, all chips
    devices: int
    op_seconds: dict                # short op name -> seconds, mean over chips
    idle_by_host: dict              # host activity -> idle seconds, mean

    def top_ops(self, n: int = TOP) -> list:
        return sorted(([k, v] for k, v in self.op_seconds.items()),
                      key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = TOP) -> list:
        return sorted(([k, v] for k, v in self.idle_by_host.items()),
                      key=lambda kv: -kv[1])[:n]

    def seconds(self, pick: Callable[[Event], bool]) -> float:
        """Device seconds of the events ``pick`` selects, mean over
        chips."""
        return sum(ev.seconds for ev in self.ops if pick(ev)) \
            / max(self.devices, 1)


def host_activity(trace: Trace, times) -> list:
    """What the host was doing at each of the sorted ``times``: the
    innermost benchmark span, and the shortest other host event (a
    Python function, a runtime call) that covers the time, if any."""
    names = []
    for span, inner in zip(innermost(trace.spans, times),
                           innermost(trace.host, times)):
        name = span.name if span is not None else "outside bench spans"
        names.append(name if inner is None else f"{name}: {inner.name}")
    return names


def reduce(trace: Trace, window: str = WINDOW) -> Reduced:
    """Busy time, op times and attributed idle time within the window."""
    spans = [s for s in trace.spans if s.name == window]
    if len(spans) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(spans)}")
    lo, hi = spans[0].start, spans[0].end
    ndev = len(trace.devices)
    if ndev == 0:
        raise ValueError("the trace holds no device operations")
    busy, ops = 0.0, []
    op_seconds = defaultdict(float)
    idle_by_host = defaultdict(float)
    for events in trace.devices:
        inside = [ev for ev in events if ev.end > lo and ev.start < hi]
        ops.extend(inside)
        for ev in inside:
            op_seconds[op_name(ev)] += (min(ev.end, hi)
                                        - max(ev.start, lo)) / ndev
        cover = merged(inside, lo, hi)
        busy += sum(e - s for s, e in cover) / ndev
        gaps = idle(cover, lo, hi)
        for (s, e), name in zip(gaps, host_activity(
                trace, [(s + e) / 2 for s, e in gaps])):
            idle_by_host[name] += (e - s) / ndev
    return Reduced(hi - lo, busy, ops, ndev, dict(op_seconds),
                   dict(idle_by_host))
