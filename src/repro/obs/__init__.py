"""repro.obs — process-wide tracing + metrics for the AES-SpMM stack.

One import surface for every subsystem::

    from repro import obs

    with obs.trace("tune", granularity="graph") as sp:
        ...
        sp.set(cache="miss")
    obs.count("sampler.edges_dropped", dropped)

Spans (``trace``/``traced``/``record_span``) land in a bounded ring on
the process :class:`Tracer` and, with ``$REPRO_PLAN_CACHE_DIR`` set, a
JSONL sink under ``<cache>/traces/``; counters/gauges/histograms live
in the process :class:`MetricsRegistry`.  ``$REPRO_OBS=0`` disables
collection with near-zero residual cost — the module-level helpers
below are all guarded on :func:`enabled`.

CLI: ``python -m repro.obs summary|export --perfetto out.json|--smoke``.
See docs/observability.md for the span model and counter catalog.

This package imports only the stdlib — every repro subsystem imports
it, so it must sit at the bottom of the dependency graph.
"""
from __future__ import annotations

import time

from repro.obs import trace as _trace_mod
from repro.obs.export import (build_trees, load_trace_dir, load_trace_file,
                              render_summary, to_perfetto, validate_tree,
                              write_perfetto)
from repro.obs.metrics import (LatencyHistogram, MetricsRegistry,
                               default_registry)
from repro.obs.trace import (NOOP_SPAN, Span, Tracer, configure,
                             current_context, default_tracer, enabled,
                             record_span, request_context, set_enabled,
                             trace, traced)

__all__ = [
    "LatencyHistogram", "MetricsRegistry", "Span", "Tracer",
    "build_trees", "configure", "count", "count_deferred",
    "current_context", "decision",
    "default_registry", "default_tracer", "enabled", "gauge",
    "load_trace_dir", "load_trace_file", "observe_us", "record_span",
    "render_summary", "request_context", "reset", "set_enabled",
    "snapshot", "to_perfetto", "trace", "traced", "validate_tree",
    "write_perfetto", "NOOP_SPAN",
]


def count(name: str, n: int = 1) -> None:
    """Increment a counter — no-op (one branch) when disabled."""
    if _trace_mod._enabled:
        default_registry().count(name, n)


def count_deferred(names: tuple, values, bound: int) -> None:
    """Add a device integer vector, each element at most ``bound``, to a
    tuple of counters in one device add, without reading it (read with
    the counters) — no-op when disabled."""
    if _trace_mod._enabled:
        default_registry().count_deferred(names, values, bound)


def gauge(name: str, value: float) -> None:
    """Set a gauge — no-op when disabled."""
    if _trace_mod._enabled:
        default_registry().gauge(name, value)


def observe_us(name: str, us: float) -> None:
    """Record into a named latency histogram — no-op when disabled."""
    if _trace_mod._enabled:
        default_registry().observe_us(name, us)


def decision(name: str, **attrs):
    """One-line decision log: a zero-duration ``<name>.decision`` span
    carrying the chosen config as attributes (the auditable record of
    what the tuner picked and why), plus a ``<name>.decisions``
    counter.  Returns the span (no-op when disabled)."""
    if not _trace_mod._enabled:
        return NOOP_SPAN
    now = time.perf_counter()
    default_registry().count(f"{name}.decisions")
    cur = current_context()
    return record_span(f"{name}.decision", now, now,
                       trace_id=cur[0] if cur else None,
                       parent_id=cur[1] if cur else None, **attrs)


def snapshot() -> dict:
    """JSON-able snapshot of every counter/gauge/histogram."""
    return default_registry().snapshot()


def reset() -> None:
    """Clear the process tracer ring and the metrics registry
    (tests/smoke only — the sink file, if any, is left in place)."""
    default_tracer().reset()
    default_registry().reset()
