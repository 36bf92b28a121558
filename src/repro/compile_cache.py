"""Persistent XLA compilation cache for the repo's entry-point scripts.

One call, :func:`enable_compile_cache`, made by a script before its first
compile — never on import of ``repro``.  ``$JAX_COMPILATION_CACHE_DIR``
places the cache from outside (JAX reads it itself); otherwise it lives at
one fixed directory inside the checkout, so a later run of the same code
finds what an earlier one compiled.  Every compile is kept, down to the
second or two a Pallas kernel takes.

The same call has ``repro.obs`` count what JAX traces and compiles
(:func:`count_compile_events`), so a retrace inside a hot loop shows up as
a rising ``jit.traces``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro import obs

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

#: JAX's monitoring events, under the installed version's names, and the
#: ``repro.obs`` counter each one adds to: a jaxpr built by tracing a
#: jitted function, and a backend compile (a persistent-cache hit
#: included; an in-memory hit emits nothing).
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.traces",
    "/jax/core/compile/backend_compile_duration": "jit.compiles",
}
_counting = False


def _on_event(event: str, _secs: float, **_kw) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        obs.count(name)


def count_compile_events() -> None:
    """Count JAX's traces and compiles into ``repro.obs`` from now on
    (once per process; a no-op while obs is disabled)."""
    global _counting
    if not _counting:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _counting = True


def enable_compile_cache() -> str:
    """Turn the persistent cache on, and count traces and compiles;
    returns the cache directory in use."""
    count_compile_events()
    path = os.environ.get(ENV_DIR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
