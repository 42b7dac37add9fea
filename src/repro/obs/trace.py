"""Nestable spans in per-thread ring buffers — the engine's tracer (§14).

One process-global :data:`TRACER` records *spans* (named intervals with
monotonic ``perf_counter_ns`` timestamps and arbitrary JSON-able
attributes) and *instants* (point events).  Every thread that emits —
the stream's host loop, the stream-checkpoint writer, the async
checkpoint saver, test threads — writes into its **own** fixed-capacity
ring buffer with no cross-thread synchronization on the hot path; a
buffer that fills drops its *oldest* events (and counts the drops), so
a long-running stream can always be traced with bounded memory.

The tracer is **off by default** and must cost nothing while off: the
only work a disabled ``span()``/``instant()`` call does is build its
kwargs dict and read one attribute (``TRACER.enabled``), returning a
shared no-op context manager — no allocation, no clock read, no lock.
Sites hotter than that guard with ``if TRACER.enabled:`` themselves
(``repro.core.plan`` does).  The disabled-path contract is pinned by
tests/test_obs.py: engine counters are bit-identical with tracing on
vs off, and the traced tiled stream stays within the benchmark's 5%
overhead guard even when *on*.

Two more ways in, both for the jax side of the engine, which this
module never imports:

- :meth:`Tracer.record` adds an interval that has already finished,
  given its duration (it ends now).  ``repro.runtime.compile_cache``
  turns JAX's compile-time reports into ``compile/*`` spans this way;
- :attr:`Tracer.annotate` is a hook: when set, every live span also
  opens ``annotate(name)`` for its lifetime.  ``compile_cache`` sets it
  to ``jax.profiler.TraceAnnotation``, so under a profiler session the
  spans land on the trace's host plane, on the device ops' clock.  With
  the tracer off the hook is never reached.

``merged()`` / ``snapshot()`` gather every thread's buffer under the
registration lock into one immutable :class:`TraceSnapshot` — the input
to ``repro.obs.export``'s Chrome-trace writer, where each thread
becomes its own track.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Optional, Tuple

__all__ = [
    "Event",
    "ThreadTrack",
    "TraceSnapshot",
    "Tracer",
    "TRACER",
    "span",
    "instant",
    "enabled",
    "enable",
    "disable",
    "reset",
    "tracing",
    "DEFAULT_CAPACITY",
]

#: per-thread ring capacity (events); a 5-span tile costs ~5 entries, so
#: the default holds a ~13k-tile stream before the ring starts dropping
DEFAULT_CAPACITY = 1 << 16


@dataclasses.dataclass(frozen=True)
class Event:
    """One recorded span or instant.

    ``ts``/``dur`` are ``perf_counter_ns`` values (``dur is None`` for
    instants); ``depth`` is the span-nesting level at entry on the
    emitting thread (0 = top level), which is how the nesting tests
    check parent/child structure without needing explicit span ids.
    """

    name: str
    ts: int
    dur: Optional[int]
    depth: int
    attrs: dict


@dataclasses.dataclass(frozen=True)
class ThreadTrack:
    """One thread's drained ring: identity + events in record order."""

    tid: int
    name: str
    events: Tuple[Event, ...]
    dropped: int


@dataclasses.dataclass(frozen=True)
class TraceSnapshot:
    """A point-in-time merge of every thread's buffer."""

    pid: int
    epoch_ns: int
    threads: Tuple[ThreadTrack, ...]

    @property
    def dropped(self) -> int:
        return sum(t.dropped for t in self.threads)

    def events(self) -> Tuple[Event, ...]:
        """All events across threads, sorted by start timestamp."""
        out = [e for t in self.threads for e in t.events]
        out.sort(key=lambda e: e.ts)
        return tuple(out)

    def named(self, name: str) -> Tuple[Event, ...]:
        return tuple(e for e in self.events() if e.name == name)


class _ThreadBuf:
    """One thread's ring: only its owner appends (no lock on the path)."""

    __slots__ = ("tid", "name", "events", "dropped", "depth", "capacity")

    def __init__(self, capacity: int):
        t = threading.current_thread()
        self.tid = t.ident
        self.name = t.name
        self.capacity = capacity
        self.events = deque(maxlen=capacity)
        self.dropped = 0
        self.depth = 0

    def push(self, ev: Event):
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)


class _NullSpan:
    """The shared disabled-path context manager (one instance, no state)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    """A live span: clock read on enter, ring append on exit (and the
    tracer's ``annotate`` hook, when set, open in between)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_buf", "_t0", "_depth",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        hook = self._tracer.annotate
        self._ann = None if hook is None else hook(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        buf = self._tracer._buf()
        self._buf = buf
        self._depth = buf.depth
        buf.depth += 1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        buf = self._buf
        buf.depth -= 1
        buf.push(Event(self._name, self._t0, t1 - self._t0, self._depth,
                       self._attrs))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    """The per-thread-ring recorder.  ``enabled`` is THE fast-path gate:
    every emit site reads it once and bails before touching anything
    else, so a disabled tracer is a single attribute load."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        #: ``name -> context manager`` opened around every live span
        #: (None: no hook)
        self.annotate = None
        self.capacity = capacity
        self.epoch_ns = time.perf_counter_ns()
        self._local = threading.local()
        self._lock = threading.Lock()
        self._bufs: list = []  # every thread's ring, registration order

    # -- per-thread buffers -------------------------------------------------
    def _buf(self) -> _ThreadBuf:
        buf = getattr(self._local, "buf", None)
        if buf is None:
            buf = _ThreadBuf(self.capacity)
            self._local.buf = buf
            with self._lock:
                self._bufs.append(buf)
        return buf

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        if not self.enabled:
            return
        buf = self._buf()
        buf.push(Event(name, time.perf_counter_ns(), None, buf.depth,
                       attrs))

    def record(self, name: str, dur_ns: int, **attrs) -> None:
        """Record a span that has just finished after ``dur_ns``: it ends
        now and starts ``dur_ns`` earlier, at the current nesting depth.
        For intervals timed by someone else and reported after the fact
        (JAX's compile phases)."""
        if not self.enabled:
            return
        buf = self._buf()
        t1 = time.perf_counter_ns()
        dur_ns = max(int(dur_ns), 0)
        buf.push(Event(name, t1 - dur_ns, dur_ns, buf.depth, attrs))

    # -- lifecycle ----------------------------------------------------------
    def enable(self, capacity: Optional[int] = None) -> None:
        """Start recording (idempotent).  ``capacity`` resizes the rings
        — existing buffers are cleared so every thread gets the new
        size on its next emit."""
        if capacity is not None and capacity != self.capacity:
            self.capacity = int(capacity)
            self.reset()
        self.enabled = True

    def disable(self) -> None:
        """Stop recording; buffers are retained for a later export."""
        self.enabled = False

    def reset(self) -> None:
        """Drop every thread's recorded events (and their rings: each
        thread re-registers a fresh ring, at the current capacity, on
        its next emit)."""
        with self._lock:
            self._bufs.clear()
        self._local = threading.local()
        self.epoch_ns = time.perf_counter_ns()

    # -- merge --------------------------------------------------------------
    def snapshot(self) -> TraceSnapshot:
        """Merge every thread's ring into one immutable snapshot.

        Taken under the registration lock; threads still *running* keep
        appending to their rings (their owner-only contract), so a
        snapshot racing a live emitter sees a prefix of that thread's
        events — exact merges are taken after workers quiesce, which is
        when the engine takes them (end of stream, ``close()``d
        writers, process exit)."""
        with self._lock:
            tracks = tuple(
                ThreadTrack(tid=b.tid, name=b.name,
                            events=tuple(b.events), dropped=b.dropped)
                for b in self._bufs)
        return TraceSnapshot(pid=os.getpid(), epoch_ns=self.epoch_ns,
                             threads=tracks)

    def stats(self) -> dict:
        """Counters for ``obs.snapshot()``: thread/event/drop totals."""
        snap = self.snapshot()
        return {"enabled": self.enabled,
                "threads": len(snap.threads),
                "events": sum(len(t.events) for t in snap.threads),
                "dropped": snap.dropped}


#: the process-global tracer every engine site emits through
TRACER = Tracer()


def span(name: str, **attrs):
    """``with span("tile/compute", tile=k): ...`` — a no-op context
    manager while tracing is off (one attribute check)."""
    if not TRACER.enabled:
        return _NULL
    return _Span(TRACER, name, attrs)


def instant(name: str, **attrs) -> None:
    """Record a point event (fault, retry, quarantine, kill)."""
    TRACER.instant(name, **attrs)


def enabled() -> bool:
    return TRACER.enabled


def enable(capacity: Optional[int] = None) -> None:
    TRACER.enable(capacity)


def disable() -> None:
    TRACER.disable()


def reset() -> None:
    TRACER.reset()


class tracing:
    """``with tracing() as snap_fn: ...`` — enable for a scope, restore
    the previous enabled state after, and hand back ``TRACER.snapshot``
    so tests read the merged events without reaching into globals."""

    def __init__(self, capacity: Optional[int] = None, fresh: bool = True):
        self._capacity = capacity
        self._fresh = fresh

    def __enter__(self):
        self._was = TRACER.enabled
        if self._fresh:
            TRACER.reset()
        TRACER.enable(self._capacity)
        return TRACER.snapshot

    def __exit__(self, *exc):
        TRACER.enabled = self._was
        return False
