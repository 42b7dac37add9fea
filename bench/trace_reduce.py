"""From a profiler trace to device busy time, collective time and the
breakdown of a window.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace`: per device, the intervals of the operations on its
op line, and the host spans that the benchmark opened with
``jax.profiler.TraceAnnotation`` (names starting ``bench/``).  ``reduce``
turns a trace into numbers, in seconds:

- busy: the union of the device's operation intervals inside the window
  (operations that overlap count once);
- collective: the union of its collective operations' intervals
  (collective-permute, all-gather, all-reduce, reduce-scatter,
  all-to-all);
- device_ops: each operation name's time inside the window, summed over
  the devices and divided by their number, most first;
- idle_gaps: the longest stretches inside the window in which a device
  ran nothing, each named by the host span (``call``, ``fetch``,
  ``submit``, ...) that overlapped it most.

The window is the host span ``bench/window`` unless one is given.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Trace", "load", "reduce", "union", "op_name", "COLLECTIVE"]

DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
#: the line of a device plane that holds one event per executed operation
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"collective-permute|all-gather|all-reduce|reduce-scatter|all-to-all",
    re.IGNORECASE)
SPAN_PREFIX = "bench/"
WINDOW = "bench/window"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    #: device ordinal -> [(start_s, end_s, op name)]
    devices: Dict[int, List[Tuple[float, float, str]]]
    #: host spans of the benchmark: [(start_s, end_s, name)]
    spans: List[Tuple[float, float, str]]

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        """A trace kept as gzip JSON ``{"devices": {ordinal: events},
        "spans": spans}`` (the recorded traces of the tests)."""
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        return cls({int(k): [tuple(e) for e in v]
                    for k, v in doc["devices"].items()},
                   [tuple(s) for s in doc["spans"]])


def op_name(event_name: str) -> str:
    """The operation's own name: a TPU op event is named by its whole HLO
    instruction (``%fusion.2 = f32[...] fusion(...)``); keep ``fusion.2``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    devices: Dict[int, list] = {}
    spans = []
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    evs.extend((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                op_name(e.name))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans)


def union(intervals) -> List[Interval]:
    """Merge intervals into disjoint ones, sorted."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, w0, w1):
    for s, e, name in events:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            yield s, e, name


def _total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _gaps(busy: List[Interval], w0: float, w1: float) -> List[Interval]:
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def _name_gap(gap: Interval, spans) -> str:
    """The host span that overlaps the gap most (the window itself only
    where nothing else does); shorter spans win ties."""
    best, best_key = "none", None
    for s, e, name in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (name != WINDOW, ov, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best[len(SPAN_PREFIX):] if best.startswith(SPAN_PREFIX) else best


def reduce(trace: Trace, window: Optional[Interval] = None,
           top: int = 10) -> dict:
    """Busy, collective and idle time per device inside the window, and
    the breakdown.  Raises when the trace has no device or no window."""
    if window is None:
        wins = [(s, e) for s, e, n in trace.spans if n == WINDOW]
        if not wins:
            raise ValueError(f"no {WINDOW!r} span in the trace")
        window = wins[0]
    w0, w1 = window
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    busy, coll, per_op, gaps = [], [], {}, []
    ordinals = sorted(trace.devices)
    for d in ordinals:
        evs = list(_clip(trace.devices[d], w0, w1))
        merged = union((s, e) for s, e, _ in evs)
        busy.append(_total(merged))
        coll.append(_total(union((s, e) for s, e, n in evs
                                 if COLLECTIVE.search(n))))
        for s, e, n in evs:
            per_op[n] = per_op.get(n, 0.0) + (e - s)
        gaps.extend(_gaps(merged, w0, w1))
    n_dev = len(ordinals)
    ops = sorted(((n, t / n_dev) for n, t in per_op.items()),
                 key=lambda p: -p[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    spans = [s for s in trace.spans if s[1] > w0 and s[0] < w1]
    return {
        "window_s": w1 - w0,
        "devices": ordinals,
        "busy_s": busy,
        "collective_s": coll,
        "device_ops": [[n, t] for n, t in ops],
        "idle_gaps": [[_name_gap(g, spans), g[1] - g[0]] for g in longest],
    }
