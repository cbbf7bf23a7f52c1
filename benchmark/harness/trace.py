"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

What the profiler writes on a TPU (looked at by hand, PR 23): one plane
per chip named ``/device:TPU:<n>``; in it the line ``XLA Modules`` holds
one event per executed program (named ``jit_<fn>(<fingerprint>)``) and the
line ``XLA Ops`` one event per HLO operation, with start and duration in
nanoseconds on one clock with the host plane ``/host:CPU``, whose lines
are threads and hold the ``TraceAnnotation`` events.  Busy time is the
union of the op intervals, so nested or overlapping events count once.
"""
from __future__ import annotations

import collections
import glob
import os
import re

from . import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")
_MODULE_ID = re.compile(r"\(\d+\)$")


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_base(name: str) -> str:
    """The ``XLA Ops`` line names an event by its whole HLO text,
    ``%fusion.3 = bf16[...] fusion(...)``: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_base(name: str) -> str:
    """``jit_step(123456)`` -> ``jit_step``."""
    return _MODULE_ID.sub("", name)


class Trace:
    """The events of one trace, in seconds, devices ordered by number."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.devices = {}          # n -> {"ops": [...], "modules": [...]}
        self.host = []             # (name, start, end) of every host event
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {ln.name: ln for ln in plane.lines}
                self.devices[int(m.group(1))] = {
                    "ops": self._events(lines.get(OPS_LINE)),
                    "modules": self._events(lines.get(MODULES_LINE))}
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    self.host.extend(self._events(ln))
        self.plane_names = [p.name for p in data.planes]

    @staticmethod
    def _events(line):
        if line is None:
            return []
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def reduce_trace(trace: Trace, chips: int,
                 cover_prefix: str = "bench.") -> dict:
    """Numbers of the traced window.

    The window is the span of the device's own events, first start to
    last end: the device's clock runs about a millisecond apart from the
    host's (seen in the recorded fixture), so a host annotation cannot
    bound it, and the drivers start and stop the profiler around just the
    stretch they mean.  ``busy_s`` is averaged over the first ``chips``
    devices; the per-module and per-op tables, collective time and idle
    gaps are those of device 0.  A gap is named by the innermost host
    annotation of the benchmark's own (``bench.*``) that covers its
    middle, which the clocks' distance can misplace for gaps under a
    millisecond.
    """
    if not trace.devices:
        raise ValueError("the trace holds no device plane "
                         f"(planes: {trace.plane_names})")
    order = sorted(trace.devices)[:chips]
    dev0 = trace.devices[order[0]]
    every = [ev for n in order for ev in trace.devices[n]["ops"]]
    if not every:
        raise ValueError("no operation ran on the device in the trace")
    lo = min(a for _n, a, _b in every)
    hi = max(b for _n, _a, b in every)

    def clipped(events):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in events
                if b > lo and a < hi]

    busy = [stats.union_seconds((a, b) for _n, a, b in
                                clipped(trace.devices[n]["ops"]))
            for n in order]
    ops0 = clipped(dev0["ops"])
    per_op = collections.Counter()
    collective = 0.0
    for n, a, b in ops0:
        n = op_base(n)
        per_op[n] += b - a
        if n.startswith(COLLECTIVE_PREFIXES):
            collective += b - a
    modules = {}
    for n, a, b in clipped(dev0["modules"]):
        m = modules.setdefault(module_base(n), {"count": 0, "total_s": 0.0})
        m["count"] += 1
        m["total_s"] += b - a
    covers = [(n, a, b) for n, a, b in trace.host
              if n.startswith(cover_prefix)]
    idle = []
    for a, b in stats.gaps([(a, b) for _n, a, b in ops0], lo, hi):
        mid = 0.5 * (a + b)
        inside = [(cb - ca, n) for n, ca, cb in covers if ca <= mid <= cb]
        idle.append((min(inside)[1] if inside else "unattributed", b - a))
    idle.sort(key=lambda g: -g[1])
    return {"window_s": hi - lo,
            "busy_s": sum(busy) / len(busy),
            "busy_s_device0": busy[0],
            "collective_s": collective,
            "modules": modules,
            "device_ops": [[n, s] for n, s in per_op.most_common(10)],
            "idle_gaps": [[n, s] for n, s in idle[:5]],
            "n_ops": len(ops0)}
