"""The by-hand tool ``gaps.py`` on made-up events: each idle gap of
device 0 goes to the innermost ``dl4j.*`` span over its middle, by the
rule ``reduce_trace`` names its five longest with."""
import pytest

import gaps
from harness import trace


def _made_up(ops, host):
    t = trace.Trace.__new__(trace.Trace)
    t.devices = {0: {"ops": ops, "modules": []}}
    t.host, t.plane_names = list(host), []
    return t


@pytest.mark.parametrize("host, want", [
    # the innermost program span wins over its parent and over bench.*
    ([("bench.window", 0.0, 10.0), ("dl4j.serving.decode.step", 0.9, 3.1),
      ("dl4j.serving.loop.emit", 1.0, 2.5),
      ("dl4j.serving.loop.wait", 5.0, 7.0)],
     {"dl4j.serving.loop.emit": 2.0, "dl4j.serving.loop.wait": 3.0}),
    # a program without annotations (the parent commit)
    ([("bench.window", 0.0, 10.0)], {"unattributed": 5.0}),
], ids=["program_spans", "no_annotations"])
def test_idle_goes_to_the_innermost_program_span(host, want):
    ops = [("%a = f32[] fusion()", 0.0, 1.0), ("%a = f32[] fusion()", 3.0, 4.0),
           ("%a = f32[] fusion()", 7.0, 8.0)]
    t = _made_up(ops, host)
    assert gaps.idle_by_span(t) == pytest.approx(want)
    named = trace.reduce_trace(t, 1, cover_prefix=gaps.PREFIX)["idle_gaps"]
    by_name = {}
    for n, s in named:
        by_name[n] = by_name.get(n, 0.0) + s
    assert by_name == pytest.approx(want)
