"""repro.obs — unified tracing + metrics for the engine (DESIGN.md §14).

One lightweight, dependency-free observability layer threaded through
every engine subsystem:

- :mod:`repro.obs.trace`   — nestable spans in per-thread ring buffers
  (``span("tile/compute", tile=k)``), a process-global tracer that is a
  no-op when disabled;
- :mod:`repro.obs.metrics` — named counters / gauges / mergeable
  fixed-bucket histograms in one registry;
- :mod:`repro.obs.export`  — Chrome ``trace_event`` JSON (per-thread
  tracks; load in ``chrome://tracing`` / Perfetto) with the metrics
  snapshot alongside;
- :mod:`repro.obs.envhook` — ``REPRO_TRACE=path.json`` captures a trace
  from any run with zero code changes.

:func:`snapshot` is the one-call view of the whole engine: plan-cache
counters (per-kind breakdown included), melt-call accounting, the
metrics registry (stream writeback/retry/quarantine/liveness counters
land there), and the tracer's own buffer stats — a plain dict, ready
for a log line or a JSON dump.
"""
from __future__ import annotations

import contextlib

from repro.obs.envhook import maybe_start as maybe_start_env_trace
from repro.obs.export import chrome_trace, write_chrome_trace
from repro.obs.metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
)
from repro.obs.trace import (
    TRACER,
    TraceSnapshot,
    Tracer,
    disable,
    enable,
    enabled,
    instant,
    reset,
    span,
    tracing,
)

__all__ = [
    # trace
    "TRACER", "Tracer", "TraceSnapshot", "span", "instant", "enabled",
    "enable", "disable", "reset", "tracing",
    # metrics
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram",
    # export / env hook
    "chrome_trace", "write_chrome_trace",
    "maybe_start_env_trace",
    # unified view
    "snapshot",
    "trace_scope",
]


@contextlib.contextmanager
def trace_scope(trace=None):
    """Interpret an entry point's ``trace=`` kwarg, one policy everywhere.

    ``None`` (the default) defers to the ``REPRO_TRACE`` env hook —
    tracing turns on only when the variable is set, and the export
    happens at process exit.  ``True`` enables the tracer for the scope
    (buffers kept for a later export); a path enables it *and* writes
    the Chrome-trace JSON there when the scope closes.  ``False`` is a
    hard off.  Enabling from a disabled state starts a fresh capture;
    nested scopes (tracer already on) keep recording into the live
    buffers so an outer scope's export sees the whole timeline.
    """
    if trace is None:
        maybe_start_env_trace()
        yield
        return
    if trace is False:
        yield
        return
    was = TRACER.enabled
    if not was:
        TRACER.reset()
    TRACER.enable()
    try:
        yield
    finally:
        TRACER.enabled = was
        if not isinstance(trace, bool):
            write_chrome_trace(str(trace))


def snapshot() -> dict:
    """The whole engine's observable state as one plain dict.

    Unifies what used to be scattered ad-hoc counters: the plan cache
    (global hit/miss/eviction + per-kind sizes), melt-call accounting,
    every registered metric (stream writeback depth, retry/quarantine
    counts, heartbeat staleness, run-latency histograms), and the
    tracer's buffer stats.  Engine imports are deferred so ``repro.obs``
    itself stays import-cycle-free and jax-free.
    """
    from repro.core.melt import melt_call_count
    from repro.core.plan import plan_cache_stats

    return {
        "plan_cache": plan_cache_stats(),
        "melt_calls": melt_call_count(),
        "metrics": REGISTRY.snapshot(),
        "trace": TRACER.stats(),
    }
