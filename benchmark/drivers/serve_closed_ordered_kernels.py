"""``serve_closed_ordered`` with the traced stretch's KERNELS kept by name.

``harness/trace.py:reduce_trace`` keeps the ten device ops that took the
most time.  A step of sixty matmuls leaves a kernel that is called once a
layer outside them, and a per-kernel share of its roofline then has
nothing to read.  The configuration names its kernels
(``trace_kernels``: ``{name: prefix of the device op's name}``); this
driver has ``reduce_trace``'s result carry, under ``"kernels"``, the calls
and the summed device time of the ops of device 0 that each prefix
matches.  Everything else — schedule, generator, window, counters,
comparison — is ``serve_closed_ordered``'s, called as it is; without
``--trace 1`` so is the whole run.

``serve_common`` reduces the trace and removes it before any reader runs,
and a PR that adds a cell may not edit it or ``harness/trace.py``: so, as
``serve_closed_ordered`` does for the schedule, this driver puts a
stand-in where ``serve_common`` looks up its ``tracelib``, for the length
of a run.  A ``benchmark`` PR that gives ``reduce_trace`` a list of op
prefixes to keep takes this file away (PERF.md section 7).
"""
from __future__ import annotations

from harness import cells, trace as tracelib

_common = cells.load_module("drivers", "serve_common")
_ordered = cells.load_module("drivers", "serve_closed_ordered")


class _KeepKernels:
    """What ``serve_common.window`` asks of ``harness.trace``, the
    reduction also counting the named kernels' ops."""

    Trace = tracelib.Trace
    newest_xplane = staticmethod(tracelib.newest_xplane)

    def __init__(self, kernels: dict):
        self.kernels = kernels

    def reduce_trace(self, trace, chips: int) -> dict:
        out = tracelib.reduce_trace(trace, chips)
        ops = trace.devices[min(trace.devices)]["ops"]
        out["kernels"] = {}
        for name, prefix in self.kernels.items():
            spans = [b - a for n, a, b in ops
                     if tracelib.op_base(n).startswith(prefix)]
            out["kernels"][name] = {"count": len(spans),
                                    "total_s": sum(spans)}
        return out


def run(cell) -> dict:
    kept = _common.tracelib
    _common.tracelib = _KeepKernels(cell.config.get("trace_kernels", {}))
    try:
        return _ordered.run(cell)
    finally:
        _common.tracelib = kept
