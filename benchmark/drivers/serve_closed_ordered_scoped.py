"""``serve_closed_ordered`` with the traced stretch's ops kept by SCOPE.

A read that is part XLA (a selection, a row gather) and part kernel has
no name of its own in a device trace: the trace names an op by its HLO
instruction (``%fusion.12``), and only a kernel's instruction carries the
kernel's name.  The program wraps such a read in a ``jax.named_scope``, so
every instruction of it carries the scope in the ``op_name`` of its
metadata in the COMPILED step, which the family's builder can print
(``configs/<family>.py:step_program_text``).  The configuration names its
scopes (``trace_scopes``: ``{name: scope}``); this driver has
``reduce_trace``'s result carry, under ``"kernels"`` as
``serve_closed_ordered_kernels`` does, for each scope the device time of
the step's instructions that carry it (the union of their intervals on
device 0: an op nested in another counts once) and its calls (the ops
whose instruction is named for the scope: its kernel, one a call).

With ``--control 1`` it also asks the reference for the family's second
control where it has one (``served_gaps(..., dense_control=True)``: the
selection left out) and prints its two numbers beside the float8
control's; ``run.py`` has room for the one.

Everything else — schedule, generator, window, counters, comparison — is
``serve_closed_ordered``'s, called as it is; without ``--trace 1`` and
``--control 1`` so is the whole run.  ``serve_common`` reduces the trace
before any reader runs and a PR that adds a cell may not edit it: so this
driver puts its stand-ins where ``serve_common`` looks up its ``tracelib``
and where the cell keeps its builder and reference, for the length of a
run (PERF.md section 7).
"""
from __future__ import annotations

import re
import types
import weakref

from harness import cells, device, stats, trace as tracelib

_common = cells.load_module("drivers", "serve_common")
_ordered = cells.load_module("drivers", "serve_closed_ordered")

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def scoped_instructions(text: str, scope: str) -> set:
    """Names of the instructions of a compiled program's text whose
    ``op_name`` lies under ``scope``."""
    found = set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and f"/{scope}/" in m.group(2) + "/":
            found.add(m.group(1))
    return found


class _KeepScopes:
    """What ``serve_common.window`` asks of ``harness.trace``, the
    reduction also keeping the named scopes' ops."""

    Trace = tracelib.Trace
    newest_xplane = staticmethod(tracelib.newest_xplane)

    def __init__(self, scopes: dict, program_text):
        self.scopes, self.program_text = scopes, program_text

    def reduce_trace(self, trace, chips: int) -> dict:
        out = tracelib.reduce_trace(trace, chips)
        out["kernels"] = {}
        if not self.scopes:
            return out
        text = self.program_text()
        ops = trace.devices[min(trace.devices)]["ops"]
        for name, scope in self.scopes.items():
            members = scoped_instructions(text, scope)
            mine = [(tracelib.op_base(n), a, b) for n, a, b in ops]
            mine = [(n, a, b) for n, a, b in mine if n in members]
            out["kernels"][name] = {
                "count": sum(1 for n, _a, _b in mine if n.startswith(scope)),
                "total_s": stats.union_seconds((a, b) for _n, a, b in mine),
                "instructions": len(members)}
        return out


class _BothControls:
    """The cell's reference, its ``served_gaps`` also asked for the
    second control; what it read is kept for the run's last lines."""

    def __init__(self, reference):
        self._reference, self.dense = reference, []

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def served_gaps(self, *args, control=False, **kw):
        g = self._reference.served_gaps(*args, control=control,
                                        dense_control=control, **kw)
        self.dense.extend(g.get("dense", []))
        return g


def run(cell) -> dict:
    built = {}
    family, reference = cell.family, cell.reference

    def build_server(*args, **kw):
        server, batcher = family.build_server(*args, **kw)
        # weakly: ``Served.free`` must be the last to hold the pools
        built["batcher"] = weakref.ref(batcher)
        return server, batcher
    kept = _common.tracelib
    _common.tracelib = _KeepScopes(
        cell.config.get("trace_scopes", {}),
        lambda: family.step_program_text(built["batcher"]()))
    cell.family = types.SimpleNamespace(build_server=build_server)
    if cell.control:
        cell.reference = _BothControls(reference)
    try:
        out = _ordered.run(cell)
        dense = getattr(cell.reference, "dense", [])
        if dense:
            device.say("control (selection left out): served_gap_max = "
                       f"{max(dense):.6g}")
            device.say("control (selection left out): served_gap_mean = "
                       f"{sum(dense) / len(dense):.6g}")
        return out
    finally:
        _common.tracelib = kept
        cell.family, cell.reference = family, reference
