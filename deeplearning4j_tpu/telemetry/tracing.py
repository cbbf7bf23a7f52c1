"""Span tracing that merges with the OpProfiler's Chrome trace.

The fork had two disconnected trace producers: ``OpProfiler.phase`` (host
phases) and ``ProfilingListener`` (per-iteration slices), each writing its
own file.  :class:`Tracer` is the one producer the whole stack reports
through: nested ``span(name, **attrs)`` contexts record chrome://tracing
"X" events on a per-thread track, and :meth:`Tracer.write_chrome_trace`
merges them with the :class:`~deeplearning4j_tpu.profiler.OpProfiler`
singleton's events into ONE file (load it at ``chrome://tracing`` or
Perfetto).

Every span also enters a ``jax.profiler.TraceAnnotation`` named
``"dl4j." + name`` for its real duration, so the host span sits on the
profiler's clock beside the XLA kernel timeline in ANY capture —
``jax.profiler.start_trace``, ``start_server``, or this package's
``profiler.start_trace``.  Outside a profiler session the annotation is a
no-op in the runtime (well under a microsecond), so there is no switch.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Callable, Deque, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "tracer", "set_tracer"]

#: what a span's name is prefixed with on the profiler's host plane — a
#: trace reduction picks the program's own spans out by it
ANNOTATION_PREFIX = "dl4j."


class _ThreadTrack(threading.local):
    def __init__(self):
        self.depth = 0


class _Span:
    """One entered region of :meth:`Tracer.span` (a plain context manager:
    the decode loop enters nine of these per token, and a generator-based
    one costs several times as much)."""

    __slots__ = ("_tracer", "_name", "_observe", "_attrs", "_start",
                 "_cpu", "_depth", "_id", "_ann", "seconds")

    def __init__(self, tr: "Tracer", name: str, observe, attrs: dict,
                 cpu: bool):
        self._tracer, self._name = tr, name
        self._observe, self._attrs = observe, attrs
        #: the thread's CPU clock at the entry, for a span that asked for
        #: it (``cpu=True``); None costs a span nothing
        self._cpu: Optional[float] = 0.0 if cpu else None
        #: the region's duration, once it has been left
        self.seconds: Optional[float] = None

    def __enter__(self) -> dict:
        tr = self._tracer
        self._start = start = time.perf_counter()
        if self._cpu is not None:
            self._cpu = time.thread_time()
        self._ann = ann = TraceAnnotation(ANNOTATION_PREFIX + self._name)
        ann.__enter__()
        tr._track.depth += 1
        self._depth = depth = tr._track.depth
        with tr._lock:
            tr._next_span_id += 1
            self._id = tr._next_span_id
            tr._live[self._id] = {"name": self._name, "start": start,
                                  "depth": depth, "attrs": self._attrs}
        return self._attrs

    def __exit__(self, *_exc) -> None:
        tr = self._tracer
        tr._track.depth -= 1
        with tr._lock:
            tr._live.pop(self._id, None)
        self._ann.__exit__(None, None, None)
        # the CPU clock is read inside the wall clock's two reads, so the
        # thread cannot have run for longer than the region lasted
        cpu = None if self._cpu is None else time.thread_time() - self._cpu
        self.seconds = seconds = time.perf_counter() - self._start
        self._attrs["depth"] = self._depth
        if cpu is not None:
            self._attrs["cpu_s"] = cpu
        tr.record_complete(self._name, self._start, seconds,
                           args=self._attrs)
        if self._observe is not None:
            if cpu is None:
                self._observe(seconds)
            else:
                self._observe(seconds, cpu)


class Tracer:
    """Nested span recorder (bounded ring — long runs can't grow it
    without limit)."""

    def __init__(self, maxEvents: int = 100_000):
        self._events: Deque[dict] = deque(maxlen=int(maxEvents))
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._track = _ThreadTrack()
        self._next_tid = 0
        # spans currently INSIDE their with-block, keyed by a unique id —
        # a crash/SIGTERM dump needs "what was the process in the middle
        # of", which the completed-event ring by definition can't hold
        self._live: dict = {}
        self._next_span_id = 0

    # -- spans ------------------------------------------------------------
    def span(self, name: str, observe: Optional[Callable] = None,
             cpu: bool = False, **attrs):
        """Time a nested region: ``with tracer().span(name) as args``.
        The body may add to ``args``; everything lands in the Chrome
        event's ``args``.  Entering reads the clock once and leaving reads
        it once, and from those two reads come the Chrome event, the
        profiler annotation ``"dl4j." + name`` and — where the phase has
        a histogram — its one observation, ``observe(seconds)``: the
        three cannot disagree.  A body that raises still closes all
        three.

        ``cpu=True`` (the decode loop's phases) reads the calling
        thread's CPU clock (``time.thread_time()``) beside each of the
        two: the seconds the thread RAN in the region land in the event's
        ``args`` as ``cpu_s`` and the observer is called
        ``observe(seconds, cpu_seconds)``; wall less CPU is the time the
        thread did not run (it blocked, or it waited for the interpreter
        or for a core)."""
        return _Span(self, name, observe, attrs, cpu)

    def record_complete(self, name: str, start: float, duration: float,
                        args: Optional[dict] = None,
                        tid: Optional[int] = None) -> None:
        """Append one complete ("X") event; ``start`` is a perf_counter
        timestamp from THIS process (shares the tracer's epoch)."""
        ev = {"name": name, "ph": "X", "pid": 1,
              "tid": tid if tid is not None else self._tid(),
              "ts": (start - self._t0) * 1e6, "dur": duration * 1e6}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def _tid(self) -> int:
        """Small stable per-thread track id, stored thread-LOCALLY (raw
        idents are pthread addresses — huge, and CPython recycles them
        after thread death, so an ident-keyed map could hand a new thread
        a dead thread's track; thread-local storage dies with its
        thread)."""
        tid = getattr(self._track, "tid", None)
        if tid is None:
            with self._lock:
                self._next_tid += 1
                tid = self._next_tid
            self._track.tid = tid
        return tid

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker event ("i" phase) — crash/rollback points."""
        ev = {"name": name, "ph": "i", "pid": 1, "s": "p",
              "tid": self._tid(),
              "ts": (time.perf_counter() - self._t0) * 1e6}
        if attrs:
            ev["args"] = attrs
        with self._lock:
            self._events.append(ev)

    # -- inspection / output ---------------------------------------------
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def open_spans(self) -> List[dict]:
        """Spans whose with-block has not exited yet (outermost first):
        name, attrs, depth, and seconds open so far.  A SIGTERM'd worker's
        final snapshot includes this — "preempted 48s into `compile`" is
        the post-mortem one-liner the completed-event ring can't give."""
        now = time.perf_counter()
        with self._lock:
            live = sorted(self._live.items())
        return [{"name": s["name"], "depth": s["depth"],
                 "open_seconds": round(now - s["start"], 6),
                 "attrs": {k: v for k, v in s["attrs"].items()}}
                for _sid, s in live]

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._live.clear()
        self._t0 = time.perf_counter()

    def write_chrome_trace(self, path: str, merge_profiler: bool = True,
                           tail: Optional[int] = None) -> None:
        """ONE merged trace file: this tracer's spans plus the OpProfiler
        singleton's phase events.  Both record ``ts`` relative to their
        own perf_counter epoch, so profiler events are SHIFTED into this
        tracer's epoch before merging — phases line up against the step
        spans they overlapped, even after an ``OpProfiler.reset()`` moved
        its zero.  ``tail`` keeps only the newest N tracer events (cheap
        periodic flushes from the training hot loop)."""
        events = self.events()
        if tail is not None:
            events = events[-int(tail):]
        if merge_profiler:
            from deeplearning4j_tpu.profiler import OpProfiler
            prof = OpProfiler._instance
            if prof is not None:
                shift = (prof._t0 - self._t0) * 1e6
                pev = list(prof._events)
                if tail is not None:
                    # the profiler list is unbounded; an unbounded merge
                    # would defeat the point of a tail-bounded flush
                    pev = pev[-int(tail):]
                events = events + [
                    dict(e, ts=e["ts"] + shift) if "ts" in e else dict(e)
                    for e in pev]
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)


_default = Tracer()


def tracer() -> Tracer:
    """The process-global tracer every subsystem records through."""
    return _default


def set_tracer(t: Tracer) -> Tracer:
    """Swap the global tracer (tests); returns the previous one."""
    global _default
    prev, _default = _default, t
    return prev
