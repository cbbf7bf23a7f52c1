"""Metrics registry: counters, gauges, histograms + Prometheus exposition.

Reference analogues: the fork's ``PerformanceListener`` / ``StatsListener``
each kept private timing state and printed it; production serving
(SURVEY.md §5.1) needs ONE spine every subsystem reports through and one
scrape surface an operator can alert on.  This module is that spine:

- :class:`MetricsRegistry` — thread-safe name → metric map with a
  process-global default (:func:`get_registry`).  All hot-path users fetch
  their metric through the idempotent ``counter()/gauge()/histogram()``
  constructors (a dict lookup under a lock — negligible next to a train
  step).
- Prometheus text exposition (:meth:`MetricsRegistry.exposition`) served
  from ``/metrics`` on both :class:`~deeplearning4j_tpu.remote.server.
  JsonModelServer` and :class:`~deeplearning4j_tpu.ui.server.UIServer`.

Naming convention (enforced by ``tools/lint_telemetry.py``): every public
metric is ``dl4j_tpu_<subsystem>_<name>``; counters end in ``_total``,
time histograms in ``_seconds``.
"""
from __future__ import annotations

import gc
import math
import re
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "get_registry", "set_registry", "gc_pause_seconds",
           "thread_role", "register_thread_role",
           "DEFAULT_BUCKETS"]

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: step/restore latencies span ~1ms (CPU toy nets) to minutes (pod-scale
#: compile) — log-spaced like the Prometheus defaults, stretched upward
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0, 120.0, 300.0)


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    if math.isnan(v):
        return "NaN"
    return repr(float(v))


def _label_str(labelnames: Sequence[str], labelvalues: Tuple[str, ...]) -> str:
    if not labelnames:
        return ""
    inner = ",".join(
        '%s="%s"' % (n, str(v).replace("\\", r"\\").replace('"', r"\"")
                     .replace("\n", r"\n"))
        for n, v in zip(labelnames, labelvalues))
    return "{" + inner + "}"


class _Metric:
    """Shared label-set bookkeeping.  One ``_Metric`` per registered name;
    per-label-set cells live in ``_cells`` keyed by the label-value tuple."""

    typ = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 maxLabelSets: int = 1000):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r} on {name}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self.maxLabelSets = int(maxLabelSets)
        self._cells: Dict[Tuple[str, ...], object] = {}
        self._lock = threading.Lock()

    def _key(self, labels: Dict[str, str]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: got labels {sorted(labels)}, "
                f"declared {sorted(self.labelnames)}")
        return tuple(str(labels[n]) for n in self.labelnames)

    def _cell(self, labels: Dict[str, str]):
        key = self._key(labels)
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                if len(self._cells) >= self.maxLabelSets:
                    # unbounded label cardinality is the classic way a
                    # metrics pipeline OOMs its own process — fail loudly
                    raise ValueError(
                        f"{self.name}: label cardinality limit "
                        f"{self.maxLabelSets} exceeded")
                cell = self._new_cell()
                self._cells[key] = cell
            return cell

    def _new_cell(self):
        raise NotImplementedError

    def expose(self) -> List[str]:
        raise NotImplementedError

    def data(self) -> dict:
        """JSON-able structural dump of this metric (type/help/labels plus
        every cell's raw state) — the unit of cross-process federation:
        workers serialize ``data()`` into snapshot files and the
        coordinator's :class:`~deeplearning4j_tpu.telemetry.federation.
        TelemetryAggregator` rebuilds and merges them."""
        with self._lock:
            items = list(self._cells.items())
        return {"type": self.typ, "help": self.help,
                "labelnames": list(self.labelnames),
                "cells": [[list(key), self._cell_data(cell)]
                          for key, cell in sorted(items)]}

    def _cell_data(self, cell):
        raise NotImplementedError

    def _header(self) -> List[str]:
        out = []
        if self.help:
            out.append(f"# HELP {self.name} {self.help}")
        out.append(f"# TYPE {self.name} {self.typ}")
        return out


class _Value:
    __slots__ = ("v", "lock")

    def __init__(self):
        self.v = 0.0
        self.lock = threading.Lock()


class _ScalarMetric(_Metric):
    """One float cell per label set (counter/gauge share this shape)."""

    def _new_cell(self) -> _Value:
        return _Value()

    def inc(self, amount: float = 1.0, **labels) -> None:
        cell = self._cell(labels)
        with cell.lock:
            cell.v += amount

    def value(self, **labels) -> float:
        cell = self._cell(labels)
        with cell.lock:
            return cell.v

    def expose(self) -> List[str]:
        out = self._header()
        with self._lock:
            items = list(self._cells.items())
        for key, cell in sorted(items):
            out.append(f"{self.name}{_label_str(self.labelnames, key)} "
                       f"{_fmt(cell.v)}")
        return out

    def _cell_data(self, cell: _Value) -> float:
        with cell.lock:
            return cell.v


class Counter(_ScalarMetric):
    typ = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError(f"{self.name}: counters only go up")
        super().inc(amount, **labels)


class Gauge(_ScalarMetric):
    typ = "gauge"

    def set(self, value: float, **labels) -> None:
        cell = self._cell(labels)
        with cell.lock:
            cell.v = float(value)

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)


class _HistCell:
    __slots__ = ("counts", "sum", "count", "lock")

    def __init__(self, nbuckets: int):
        self.counts = [0] * (nbuckets + 1)     # +1 for +Inf
        self.sum = 0.0
        self.count = 0
        self.lock = threading.Lock()


class Histogram(_Metric):
    typ = "histogram"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 maxLabelSets: int = 1000):
        super().__init__(name, help, labelnames, maxLabelSets)
        bs = sorted(float(b) for b in buckets)
        if not bs:
            raise ValueError(f"{self.name}: need at least one bucket")
        self.buckets = tuple(bs)
        # the tuple the bounds were given as: an accessor that asks again
        # with the same one (a module's constant, on a hot path) is known
        # to agree without sorting it again
        self._given = buckets if isinstance(buckets, tuple) else None

    def _new_cell(self) -> _HistCell:
        return _HistCell(len(self.buckets))

    def observe(self, value: float, **labels) -> None:
        self.observe_cell(self._cell(labels), float(value))

    def cell(self, **labels) -> _HistCell:
        """The cell of one label set, for a caller that observes into it
        many times a second (``observe_cell``) and must not pay the label
        lookup each time.  It stays this histogram's until the histogram
        leaves its registry."""
        return self._cell(labels)

    def observe_cell(self, cell: _HistCell, v: float) -> None:
        i = len(self.buckets)
        for j, b in enumerate(self.buckets):
            if v <= b:
                i = j
                break
        with cell.lock:
            cell.counts[i] += 1
            cell.sum += v
            cell.count += 1

    def count(self, **labels) -> int:
        cell = self._cell(labels)
        with cell.lock:
            return cell.count

    def sum(self, **labels) -> float:
        cell = self._cell(labels)
        with cell.lock:
            return cell.sum

    def data(self) -> dict:
        out = super().data()
        out["buckets"] = list(self.buckets)
        return out

    def _cell_data(self, cell: _HistCell) -> dict:
        with cell.lock:
            return {"counts": list(cell.counts), "sum": cell.sum,
                    "count": cell.count}

    def bucketCounts(self, **labels) -> Dict[float, int]:
        """CUMULATIVE per-upper-bound counts (Prometheus ``le`` semantics),
        +Inf included."""
        cell = self._cell(labels)
        with cell.lock:
            raw = list(cell.counts)
        out, acc = {}, 0
        for b, c in zip(self.buckets + (math.inf,), raw):
            acc += c
            out[b] = acc
        return out

    def expose(self) -> List[str]:
        out = self._header()
        with self._lock:
            items = list(self._cells.items())
        for key, cell in sorted(items):
            with cell.lock:
                raw, s, n = list(cell.counts), cell.sum, cell.count
            acc = 0
            for b, c in zip(self.buckets + (math.inf,), raw):
                acc += c
                lv = key + (_fmt(b),)
                out.append(
                    f"{self.name}_bucket"
                    f"{_label_str(self.labelnames + ('le',), lv)} {acc}")
            out.append(f"{self.name}_sum{_label_str(self.labelnames, key)} "
                       f"{_fmt(s)}")
            out.append(f"{self.name}_count{_label_str(self.labelnames, key)} "
                       f"{n}")
        return out


class MetricsRegistry:
    """Thread-safe name → metric map with idempotent constructors."""

    def __init__(self):
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()
        # the process's two garbage-collection series, a pair of cells a
        # generation, once this registry is the process's (``_gc_series``)
        self._gcCells: Optional[list] = None
        # run at the top of snapshot() and exposition(), before any lock
        # of this registry is taken: for series that are read off the
        # process when somebody looks and never written on a hot path
        self._collectHooks: List = []

    def add_collect_hook(self, fn) -> None:
        """``fn(registry)`` runs whenever this registry is read whole
        (``snapshot()``, ``exposition()``), on the reader's thread."""
        if fn not in self._collectHooks:
            self._collectHooks.append(fn)

    def _collect(self) -> None:
        for fn in list(self._collectHooks):
            fn(self)

    def _gc_series(self) -> None:
        """Give this registry the process's garbage-collection series.
        The cells are made here, by a thread that holds no lock, because
        the collector's hook (``_on_gc``) may take none: a collection
        starts on whatever thread allocates, and that thread may hold
        this registry's lock or a metric's at that instant."""
        sec = self.counter(
            "dl4j_tpu_process_gc_pause_seconds_total",
            "Seconds the interpreter's garbage collector held every "
            "thread of this process, by generation (two clock reads a "
            "collection, from gc.callbacks)",
            labelnames=("generation",))
        n = self.counter(
            "dl4j_tpu_process_gc_collections_total",
            "Collections the interpreter's garbage collector ran, by "
            "generation",
            labelnames=("generation",))
        self._gcCells = [(sec._cell({"generation": g}),
                          n._cell({"generation": g})) for g in range(3)]

    def _register(self, cls, name: str, help: str, labelnames, **kw):
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls):
                    raise ValueError(
                        f"{name} already registered as {existing.typ}, "
                        f"not {cls.typ}")
                if tuple(labelnames) != existing.labelnames:
                    raise ValueError(
                        f"{name}: labelnames {tuple(labelnames)} != "
                        f"registered {existing.labelnames}")
                buckets = kw.get("buckets")
                if buckets is not None and buckets is not existing._given \
                        and tuple(sorted(
                            float(b) for b in buckets)) != existing.buckets:
                    # silently observing into someone else's bounds would
                    # leave the caller's expected le series empty
                    raise ValueError(
                        f"{name}: buckets {tuple(buckets)} != registered "
                        f"{existing.buckets}")
                return existing
            m = cls(name, help, labelnames, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram, name, help, labelnames,
                              buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._metrics)

    def unregister(self, name: str) -> None:
        with self._lock:
            self._metrics.pop(name, None)

    def clear(self) -> None:
        """Drop every metric (tests; the process-global default registry
        would otherwise leak state across test cases)."""
        with self._lock:
            self._metrics.clear()
        if self._gcCells is not None:
            self._gc_series()

    def exposition(self) -> str:
        """Prometheus text format, trailing newline included."""
        self._collect()
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> Dict[str, dict]:
        """JSON-able {name: metric.data()} dump of every registered metric
        — what :class:`~deeplearning4j_tpu.telemetry.federation.
        SnapshotWriter` persists and the aggregator merges."""
        self._collect()
        with self._lock:
            metrics = [(n, self._metrics[n]) for n in sorted(self._metrics)]
        return {n: m.data() for n, m in metrics}


_default = MetricsRegistry()
_default_lock = threading.Lock()

# -- what the collector cost, and when ------------------------------------
# gc.callbacks runs inside the collection, on the thread whose allocation
# started it, so the hook takes no lock and asks the registry for nothing:
# it adds to cells the process's registry made beforehand (nobody else
# writes them, and a reader's one attribute read cannot tear), and keeps
# the end and length of the recent collections of a millisecond or more
# for whoever asks what a stall coincided with.
_gc_started = [0.0]
_gc_recent: Deque[Tuple[float, float]] = deque(maxlen=64)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_started[0] = time.perf_counter()
        return
    end = time.perf_counter()
    seconds = end - _gc_started[0]
    cells = _default._gcCells
    if cells is not None:
        pause, collections = cells[info["generation"]]
        pause.v += seconds
        collections.v += 1
    if seconds >= 1e-3:
        _gc_recent.append((end, seconds))


def gc_pause_seconds(since: float) -> float:
    """Seconds of garbage collection that ended after ``since`` (a
    ``time.perf_counter()`` reading), among the last 64 collections of a
    millisecond or more: what a stall can be held against."""
    return sum(s for end, s in list(_gc_recent) if end >= since)


# -- CPU time by thread role ---------------------------------------------
# Nothing writes this series on a hot path: whoever reads the process's
# registry whole pays for one read of every live Python thread's CPU clock
# (``clock_gettime``: one system call a thread, no ``/proc``; on
# the benchmark's gVisor host a walk over the tasks' files cost 36-76 ms
# and held a device-to-host copy up for up to 0.18 s, PR 50), and what
# each thread gained since it was last seen is added to its role's counter.
# A thread says its role by the prefix of its name; whoever makes the
# thread registers the prefix (``register_thread_role``).

_CPU_SERIES = "dl4j_tpu_process_thread_cpu_seconds_total"
#: ``[prefix of threading.Thread.name, role]``, first match wins; a thread
#: under no prefix is ``python_other``.  These are this package's own
#: threads; other packages add theirs where they make them
_thread_roles: List[Tuple[str, str]] = [
    ("telemetry-", "telemetry"),   # snapshot writers, health, exports
    ("metrics-retention", "telemetry"),        # telemetry/timeseries.py
    ("otlp-exporter", "telemetry"),            # telemetry/otlp.py
]
_thread_lock = threading.Lock()
#: thread -> ns of CPU when last seen; a thread that has ended is dropped
_thread_seen: Dict[threading.Thread, int] = {}


def register_thread_role(prefix: str, role: str) -> None:
    """Threads whose name starts with ``prefix`` are booked under
    ``role``; said once, by the module that makes them."""
    if (prefix, role) not in _thread_roles:
        _thread_roles.append((prefix, role))


def thread_role(name: str) -> str:
    """The role a Python thread of that name is booked under."""
    for prefix, role in _thread_roles:
        if name.startswith(prefix):
            return role
    return "python_other"


def _cpu_clock_ns(thread: threading.Thread) -> Optional[int]:
    """A Python thread's CPU clock, read from any thread: Linux's clock
    id of a task, made from its kernel id (the id
    ``pthread_getcpuclockid`` hands out, without going through a
    ``pthread_t`` that may have gone stale: a task that has ended is a
    plain EINVAL); None where it has ended or the platform has no such
    clock."""
    tid = thread.native_id
    try:
        return None if tid is None else time.clock_gettime_ns((~tid << 3) | 6)
    except (AttributeError, OSError, OverflowError):
        return None


def _collect_thread_times(registry: "MetricsRegistry") -> None:
    """The collect hook of the process's registry.  A registry that is no
    longer the process's (a test swapped it out) books nothing: a
    thread's gain is booked once.  The clocks are read under the lock
    that guards what was last seen, so two readers at once cannot set an
    older reading against a newer one."""
    if registry is not _default:
        return
    gained: Dict[str, int] = {}
    with _thread_lock:
        threads = threading.enumerate()
        for th in threads:
            now = _cpu_clock_ns(th)
            if now is None:
                continue
            was = _thread_seen.get(th, 0)
            if now > was:
                role = thread_role(th.name)
                gained[role] = gained.get(role, 0) + now - was
                _thread_seen[th] = now
        for th in set(_thread_seen).difference(threads):
            del _thread_seen[th]
    if not gained:
        return
    cpu = registry.counter(
        _CPU_SERIES,
        "Seconds the process's Python threads ran on a CPU, by role, from "
        "each thread's own CPU clock, added up when the registry is read: "
        "serving_loop (a continuous batcher's loop thread), "
        "serving_handler (the HTTP fronts' request threads), telemetry "
        "(retention sampler, OTLP exporter, snapshot writers, health "
        "monitor and probes), python_other.  The runtime's own threads "
        "are no Python threads and are not counted; a thread that ends "
        "between two reads loses what it gained since the first (a "
        "request thread lives one connection: serving_handler reads "
        "low); absent where the platform has no per-thread CPU clock",
        labelnames=("role",))
    for role, ns in gained.items():
        cpu.inc(ns * 1e-9, role=role)


def _process_series(registry: MetricsRegistry) -> None:
    """Give ``registry`` the series that belong to the process."""
    registry._gc_series()
    registry.add_collect_hook(_collect_thread_times)


_process_series(_default)
gc.callbacks.append(_on_gc)


def get_registry() -> MetricsRegistry:
    """The process-global default registry (what ``/metrics`` serves)."""
    return _default


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Swap the process-global registry (tests); returns the previous one."""
    global _default
    if registry._gcCells is None:
        _process_series(registry)
    with _default_lock:
        prev, _default = _default, registry
    return prev
