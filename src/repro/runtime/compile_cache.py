"""JAX's persistent compilation cache, and what the program's compiles cost.

Where the cache lives
---------------------
Entry points that compile real work (``chip_smoke.py``, the benchmark
CLIs) call :func:`place_compile_cache` once, before anything compiles.
Library import and the tests never do: a test run keeps whatever cache
its environment chose.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set — JAX reads it
itself, and nothing here sets another directory.  Otherwise the cache
goes to ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path
is fixed on purpose: it is part of what the cache is keyed on, so a
temp name, a pid or a timestamp would never hit.

Compile accounting (DESIGN.md §14)
----------------------------------
JAX reports every compile through ``jax.monitoring``: a duration for
each of tracing (``jaxpr_trace_duration``), lowering to MLIR, Mosaic
included (``jaxpr_to_mlir_module_duration``) and the backend compile
(``backend_compile_duration``; on a persistent-cache hit, the
retrieval), each with ``fun_name`` and reported on the compiling thread
when the phase ends; and the events ``cache_hits`` / ``cache_misses``,
which fire on that thread before its backend phase ends.
:func:`install` (run when ``repro.core.plan`` is imported) listens to
them and counts a compile only when the compiling thread has an
*owner*: a plan's first dispatch (``StencilPlan.__call__`` and kin
while the executor has never traced) owns its compiles as the plan's
``kind``.

Compiles with no owner — a caller's own jits, a benchmark's reference —
are not counted.  Into ``repro.obs.REGISTRY``, always on:

- histograms (seconds) ``compile/trace_s``, ``compile/lower_s``,
  ``compile/backend_s``: one entry per phase, holding its *self* time;
- counters ``compile/cache_hits``, ``compile/cache_misses``.

Self time keeps the sums from overlapping.  Phases on one thread nest
(an inner jit traced inside an outer trace; an eager op compiled while
tracing), and each is reported when it ends, so an enclosing phase
subtracts every interval that ended inside it.  The three sums then add
up to no more than the wall time they cover.

With the tracer on, each owned phase is also a span, recorded after the
fact (:meth:`repro.obs.trace.Tracer.record`): ``compile/trace``,
``compile/lower`` and ``compile/backend`` (attrs ``fun_name``,
``owner``, and ``cache=hit|miss`` on backend spans).

:func:`install` also sets the tracer's ``annotate`` hook to
``jax.profiler.TraceAnnotation``: while the tracer is on, every live
``repro.obs`` span lands on a profiler session's host plane, on the
clock of the device ops.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from pathlib import Path

import jax
from jax import monitoring

from repro.obs.metrics import REGISTRY
from repro.obs.trace import TRACER

__all__ = ["place_compile_cache", "install", "owned", "PHASES",
           "CACHE_COUNTERS", "TUNE_HIST", "EDGES_S"]

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (this file is ``<root>/src/repro/runtime/...``)
CHECKOUT = Path(__file__).resolve().parents[3]


def place_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# -- compile accounting ------------------------------------------------------

#: JAX's duration event -> (registry histogram, span name)
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile/trace_s", "compile/trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile/lower_s", "compile/lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compile/backend_s", "compile/backend"),
}
#: JAX's persistent-cache event -> (registry counter, span attr)
CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": ("compile/cache_hits", "hit"),
    "/jax/compilation_cache/cache_misses": ("compile/cache_misses", "miss"),
}
TUNE_HIST = "tune/measure_s"
#: histogram bucket edges, seconds
EDGES_S = (0.001, 0.01, 0.1, 1.0, 10.0, 100.0)

#: per thread: ``owner`` (str or None), ``covered`` (intervals already
#: accounted, in end order), ``cache`` (the backend phase's hit/miss)
_local = threading.local()
_install_lock = threading.Lock()
_installed = False


def _register_metrics() -> None:
    for hist, _ in PHASES.values():
        REGISTRY.histogram(hist, EDGES_S)
    # never observed: kept so the benchmark's tune_s.setup reads 0.0
    REGISTRY.histogram(TUNE_HIST, EDGES_S)
    for name, _ in CACHE_COUNTERS.values():
        REGISTRY.counter(name)


def install() -> None:
    """Listen to JAX's compile reports and bridge spans to the profiler.

    Idempotent: the listeners are registered once per process; a second
    call only re-creates the metrics (after a registry reset)."""
    global _installed
    with _install_lock:
        if not _installed:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            TRACER.annotate = jax.profiler.TraceAnnotation
            _installed = True
    _register_metrics()


@contextlib.contextmanager
def owned(name: str):
    """Count the compiles this thread runs in the scope, as ``name``'s.

    Scopes nest (an inner plan's first dispatch inside an outer one's
    trace); the innermost names the owner, and the record of accounted
    intervals lives until the outermost scope closes."""
    prev = getattr(_local, "owner", None)
    if prev is None:
        _local.covered = []
    _local.owner = name
    try:
        yield
    finally:
        _local.owner = prev
        if prev is None:
            _local.covered = []


def _cover(t0: int, t1: int) -> int:
    """Account the interval ``[t0, t1)`` on this thread and return its
    self time: its length less every accounted interval that ended
    inside it (those it now stands for)."""
    cov = _local.covered
    inner = 0
    while cov and cov[-1][1] > t0:
        a, b = cov.pop()
        inner += min(b, t1) - max(a, t0)
    cov.append((t0, t1))
    return max(0, (t1 - t0) - inner)


def _on_event(event: str, **kwargs) -> None:
    hit = CACHE_COUNTERS.get(event)
    if hit is None:
        return
    if getattr(_local, "owner", None) is None:
        return
    name, _local.cache = hit
    REGISTRY.counter(name).inc()


def _on_duration(event: str, secs: float, **kwargs) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    owner = getattr(_local, "owner", None)
    if owner is None:
        return
    t1 = time.perf_counter_ns()
    dur = int(secs * 1e9)
    self_ns = _cover(t1 - dur, t1)
    hist, span_name = phase
    attrs = {"fun_name": str(kwargs.get("fun_name", "")), "owner": owner}
    if span_name == "compile/backend":
        attrs["cache"] = getattr(_local, "cache", None) or "miss"
        _local.cache = None
    REGISTRY.histogram(hist, EDGES_S).observe(self_ns / 1e9)
    TRACER.record(span_name, dur, **attrs)
