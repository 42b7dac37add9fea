"""Chrome ``trace_event`` JSON export (§14).

A :class:`~repro.obs.trace.TraceSnapshot` becomes a JSON file loadable
in ``chrome://tracing`` / `Perfetto <https://ui.perfetto.dev>`_:

- every engine thread is its own **track** — the stream's host loop,
  the stream-checkpoint writer, the async checkpoint saver — so a
  tiled stream renders as the intended pipeline diagram (``tile/read``
  → ``tile/h2d`` → ``tile/execute`` → ``tile/writeback`` →
  ``ckpt/*`` overlapping across tiles and threads);
- spans are complete events (``"ph": "X"``) with microsecond ``ts``
  relative to the tracer's epoch; instants (faults, retries,
  quarantines) are ``"ph": "i"`` thread-scoped marks.  **Every**
  emitted event — instants included — carries the full
  ``name/ts/dur/pid/tid`` field set (instants with ``dur: 0``), which
  is the schema ``tools/trace_check.py`` validates;
- each referenced ``tid`` gets a ``thread_name`` metadata event, and
  tids are remapped to small stable ints in first-seen order (0 is the
  first-registered thread — the main thread in practice) so tracks
  sort deterministically;
- the current metrics-registry snapshot rides along under
  ``otherData.metrics`` (viewers ignore it; ``trace_check`` and humans
  read it), so one file carries both the timeline and the counters.
"""
from __future__ import annotations

import json
from typing import Optional

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

__all__ = [
    "chrome_trace",
    "write_chrome_trace",
    "TRACE_EVENT_VERSION",
]

#: bumped when the exported event schema changes (trace_check pins it)
TRACE_EVENT_VERSION = 1


def _us(ns: int, epoch_ns: int) -> float:
    return (ns - epoch_ns) / 1e3


def chrome_trace(snap: Optional[_trace.TraceSnapshot] = None,
                 metrics_snapshot: Optional[dict] = None) -> dict:
    """The Chrome ``trace_event`` payload (JSON-object format) for a
    trace snapshot (default: the global tracer's current buffers)."""
    if snap is None:
        snap = _trace.TRACER.snapshot()
    if metrics_snapshot is None:
        metrics_snapshot = _metrics.snapshot()
    events = []
    tid_map = {}  # real thread ident -> small stable int, first-seen
    for track in snap.threads:
        tid = tid_map.setdefault(track.tid, len(tid_map))
        events.append({
            "ph": "M", "name": "thread_name", "pid": snap.pid, "tid": tid,
            "args": {"name": track.name},
        })
        events.append({
            "ph": "M", "name": "thread_sort_index", "pid": snap.pid,
            "tid": tid, "args": {"sort_index": tid},
        })
        for ev in track.events:
            rec = {
                "name": ev.name,
                "ph": "X" if ev.dur is not None else "i",
                "ts": _us(ev.ts, snap.epoch_ns),
                "dur": (_us(ev.ts + ev.dur, snap.epoch_ns)
                        - _us(ev.ts, snap.epoch_ns))
                       if ev.dur is not None else 0.0,
                "pid": snap.pid,
                "tid": tid,
                "args": dict(ev.attrs, depth=ev.depth),
            }
            if ev.dur is None:
                rec["s"] = "t"  # thread-scoped instant
            events.append(rec)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "version": TRACE_EVENT_VERSION,
            "dropped_events": snap.dropped,
            "metrics": metrics_snapshot,
        },
    }


def write_chrome_trace(path: str,
                       snap: Optional[_trace.TraceSnapshot] = None,
                       metrics_snapshot: Optional[dict] = None) -> str:
    """Write the Chrome-trace JSON for ``snap`` to ``path``; returns the
    path.  Load it in ``chrome://tracing`` or https://ui.perfetto.dev."""
    payload = chrome_trace(snap, metrics_snapshot)
    with open(path, "w") as fh:
        json.dump(payload, fh)
    return str(path)
