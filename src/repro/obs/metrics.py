"""Process-wide metrics: counters, gauges, latency histograms.

The registry is a flat, thread-safe namespace of named metrics with a
JSON-able ``snapshot()`` surface.  Counters are monotonically increasing
ints, gauges are last-write-wins floats, histograms are the log-spaced
``LatencyHistogram`` that the serving telemetry has always used — it
lives here now (``serving.telemetry`` re-exports it for compatibility)
and carries its own lock so standalone concurrent ``record()`` is safe.

A counter may also take device integers that are not read when they are
added (:meth:`MetricsRegistry.count_deferred`): a count the device
computes costs the hot path no host sync, and is read when the counters
are.

Naming convention: dotted lowercase, subsystem first —
``sampler.edges_dropped``, ``plan_cache.hit_memory``,
``executor.run_ell.pallas.quant``.  See docs/observability.md for the
full catalog.
"""
from __future__ import annotations

import math
import threading
from typing import Dict, Iterable


class LatencyHistogram:
    """Fixed-memory latency histogram with log-spaced buckets.

    Buckets span ``[lo_us, hi_us)`` with ``per_decade`` buckets per decade
    (default: 1us .. 1000s at 8/decade = 72 buckets); underflow clamps
    into the first bucket, overflow into the last.  Percentiles are read
    back with log-linear interpolation inside the hit bucket, which keeps
    the p99 honest to within one bucket's ratio (~33% at 8/decade) while
    the exact min/max/mean are tracked separately.

    Historically lived in ``repro.serving.telemetry`` (which still
    re-exports it); moving here added an internal lock so standalone
    concurrent ``record()`` is safe without an external wrapper.
    """

    def __init__(self, lo_us: float = 1.0, hi_us: float = 1e9,
                 per_decade: int = 8):
        if not (0 < lo_us < hi_us):
            raise ValueError(f"need 0 < lo_us < hi_us, got {lo_us}, {hi_us}")
        self.lo_us = float(lo_us)
        self.hi_us = float(hi_us)
        decades = math.log10(hi_us / lo_us)
        self.num_buckets = max(int(math.ceil(decades * per_decade)), 1)
        self._log_lo = math.log10(lo_us)
        self._scale = self.num_buckets / decades   # buckets per log10 unit
        self.counts = [0] * self.num_buckets
        self.count = 0
        self.sum_us = 0.0
        self.min_us = math.inf
        self.max_us = 0.0
        self._mu = threading.Lock()

    def _bucket(self, us: float) -> int:
        if us <= self.lo_us:
            return 0
        idx = int((math.log10(us) - self._log_lo) * self._scale)
        return min(idx, self.num_buckets - 1)

    def _edges(self, idx: int) -> tuple:
        lo = 10.0 ** (self._log_lo + idx / self._scale)
        hi = 10.0 ** (self._log_lo + (idx + 1) / self._scale)
        return lo, hi

    def record(self, us: float) -> None:
        us = float(us)
        if not (us >= 0.0 and math.isfinite(us)):
            return
        with self._mu:
            self.counts[self._bucket(us)] += 1
            self.count += 1
            self.sum_us += us
            self.min_us = min(self.min_us, us)
            self.max_us = max(self.max_us, us)

    def _percentile_locked(self, p: float) -> float:
        if self.count == 0:
            return 0.0
        target = max(min(p, 100.0), 0.0) / 100.0 * self.count
        seen = 0
        for idx, c in enumerate(self.counts):
            if c == 0:
                continue
            if seen + c >= target:
                frac = (target - seen) / c
                lo, hi = self._edges(idx)
                us = 10.0 ** (math.log10(lo)
                              + frac * (math.log10(hi) - math.log10(lo)))
                return float(min(max(us, self.min_us), self.max_us))
            seen += c
        return float(self.max_us)

    def percentile(self, p: float) -> float:
        """The ``p``-th percentile (0..100) in microseconds, log-linearly
        interpolated inside the hit bucket and clamped to the observed
        min/max; 0.0 on an empty histogram."""
        with self._mu:
            return self._percentile_locked(p)

    @property
    def mean_us(self) -> float:
        with self._mu:
            return self.sum_us / self.count if self.count else 0.0

    def snapshot(self) -> dict:
        with self._mu:
            mean = self.sum_us / self.count if self.count else 0.0
            return {
                "count": self.count,
                "mean_us": round(mean, 1),
                "min_us": round(self.min_us, 1) if self.count else 0.0,
                "p50_us": round(self._percentile_locked(50), 1),
                "p95_us": round(self._percentile_locked(95), 1),
                "p99_us": round(self._percentile_locked(99), 1),
                "max_us": round(self.max_us, 1),
            }

    def reset(self) -> None:
        with self._mu:
            self.counts = [0] * self.num_buckets
            self.count = 0
            self.sum_us = 0.0
            self.min_us = math.inf
            self.max_us = 0.0


#: Largest value a deferred counter's device sum may reach (int32).
DEVICE_SUM_MAX = 2**31 - 1


class _Deferred:
    """The unread device integers of one counter: a running device sum
    with room for ``room`` more, and the sum sealed before it."""

    __slots__ = ("total", "room", "sealed")

    def __init__(self):
        self.total = self.sealed = None
        self.room = DEVICE_SUM_MAX


class MetricsRegistry:
    """Thread-safe flat namespace of counters / gauges / histograms."""

    def __init__(self):
        # reentrant: a deferred count's device add may trace, and JAX's
        # trace listener counts into this registry on the same thread
        self._mu = threading.RLock()
        self._counters: Dict[str, int] = {}
        self._deferred: Dict[tuple, _Deferred] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, LatencyHistogram] = {}

    def count(self, name: str, n: int = 1) -> None:
        with self._mu:
            self._counters[name] = self._counters.get(name, 0) + int(n)

    def count_deferred(self, names: tuple, values, bound: int) -> None:
        """Add ``values``, a device integer vector of one element per
        counter in ``names``, each at most ``bound``, to those counters
        without reading it: one device add serves them all.

        Values fold into one running device sum per tuple of counters
        (``+`` on the device, no sync).  Before the sum could pass
        :data:`DEVICE_SUM_MAX` it is sealed, its copy to the host begun,
        and a fresh sum started; a sealed sum is read at the next seal,
        long after the device wrote it.  Every unread value is read by
        the next :meth:`counter_value`, :meth:`counters` or
        :meth:`snapshot`."""
        with self._mu:
            d = self._deferred.get(names)
            if d is None:
                d = self._deferred[names] = _Deferred()
            if d.total is not None and bound > d.room:
                if d.sealed is not None:
                    self._add_locked(names, d.sealed)
                d.sealed, d.total, d.room = d.total, None, DEVICE_SUM_MAX
                start_copy = getattr(d.sealed, "copy_to_host_async", None)
                if start_copy is not None:
                    start_copy()
            d.total = values if d.total is None else d.total + values
            d.room -= bound

    def _add_locked(self, names: tuple, values) -> None:
        for name, v in zip(names, values.tolist()):
            self._counters[name] = self._counters.get(name, 0) + v

    def _resolve_locked(self) -> None:
        """Read every deferred value into its counter."""
        for names, d in self._deferred.items():
            for values in (d.sealed, d.total):
                if values is not None:
                    self._add_locked(names, values)
        self._deferred.clear()

    def gauge(self, name: str, value: float) -> None:
        with self._mu:
            self._gauges[name] = float(value)

    def histogram(self, name: str) -> LatencyHistogram:
        """Get-or-create a histogram (safe to call from any thread)."""
        with self._mu:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = LatencyHistogram()
            return h

    def observe_us(self, name: str, us: float) -> None:
        self.histogram(name).record(us)

    def counter_value(self, name: str) -> int:
        with self._mu:
            self._resolve_locked()
            return self._counters.get(name, 0)

    def gauge_value(self, name: str, default: float = 0.0) -> float:
        with self._mu:
            return self._gauges.get(name, default)

    def counters(self, prefix: str = "") -> Dict[str, int]:
        with self._mu:
            self._resolve_locked()
            return {k: v for k, v in sorted(self._counters.items())
                    if k.startswith(prefix)}

    def snapshot(self) -> dict:
        """JSON-able view of every metric."""
        with self._mu:
            self._resolve_locked()
            counters = dict(sorted(self._counters.items()))
            gauges = dict(sorted(self._gauges.items()))
            hists = {k: h for k, h in sorted(self._hists.items())}
        # histogram snapshots take each histogram's own lock; never
        # nested inside the registry lock (lock order: registry > hist
        # would be fine too, but keeping them disjoint is simpler).
        return {
            "counters": counters,
            "gauges": gauges,
            "histograms": {k: h.snapshot() for k, h in hists.items()},
        }

    def reset(self, names: Iterable[str] = ()) -> None:
        """Clear everything (or just the named metrics; the deferred
        sums, which may hold other counters too, are read first)."""
        with self._mu:
            if not names:
                self._counters.clear()
                self._deferred.clear()
                self._gauges.clear()
                self._hists.clear()
                return
            self._resolve_locked()
            for n in names:
                self._counters.pop(n, None)
                self._gauges.pop(n, None)
                self._hists.pop(n, None)


_DEFAULT = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    return _DEFAULT
