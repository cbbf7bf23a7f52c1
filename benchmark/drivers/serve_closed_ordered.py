"""``serve_closed`` with the order of the lengths fixed by the traffic mix.

What is measured and how is ``serve_closed``'s and ``serve_common``'s,
called as they are.  The schedule is ``loadgen/ordered.py``'s (the mix's
lengths in the order its ``order_seed`` names, the prompts' tokens from
``--seed``) and the generator's process is ``loadgen/main_ordered.py``
(that schedule, the clients started ``stagger_s`` apart), so that every
run's window holds the same prefills: see the two files for why.

``serve_common`` names its schedule module and starts its generator by a
path of its own; a PR that adds a cell may not edit it, so for the length
of a run this driver puts its two stand-ins where ``serve_common`` looks
them up.  A ``benchmark`` PR that lets the traffic mix name its generator
takes this file and the stand-ins away (PERF.md section 7).
"""
from __future__ import annotations

import os
import subprocess

from harness import cells

_common = cells.load_module("drivers", "serve_common")
_closed = cells.load_module("drivers", "serve_closed")

import ordered  # noqa: E402  (loadgen/ is on the path once serve_common is loaded)

_MAIN = os.path.join(cells.BENCH_DIR, "loadgen", "main_ordered.py")


class _Generator:
    """What ``serve_common.window`` asks of ``subprocess``: the same call
    and arguments, the process started from ``main_ordered.py``."""

    PIPE = subprocess.PIPE

    @staticmethod
    def Popen(cmd, **kwargs):
        if os.path.basename(cmd[1]) != "main.py":
            raise RuntimeError(f"not the load generator's command: {cmd[:2]}")
        return subprocess.Popen([cmd[0], _MAIN] + list(cmd[2:]), **kwargs)


def run(cell) -> dict:
    kept = _common.schedule, _common.subprocess
    _common.schedule, _common.subprocess = ordered, _Generator
    try:
        return _common.run(cell, _closed.measure)
    finally:
        _common.schedule, _common.subprocess = kept
