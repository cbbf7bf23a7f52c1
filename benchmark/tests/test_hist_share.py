"""The reader ``hist_share`` against synthetic pairs of snapshots: the
share of the window that the selected series gained, a histogram that
gained nothing (0), a program without the histogram (the parent commit:
nothing to read), the two lines it says; and the four per-layer metrics
of PR 36 against the window of a tiny run of each serving driver on the
CPU (the counters are there without a trace)."""
import json

import pytest

import run
import tiny
from harness import cells

IDLE = "dl4j_tpu_serving_device_idle_seconds"
LOOP = "dl4j_tpu_serving_loop_phase_seconds"
GC_S = "dl4j_tpu_process_gc_pause_seconds_total"
GC_N = "dl4j_tpu_process_gc_collections_total"
BOUNDS = [0.01, 0.1, 1.0]


def _hist(labels, series: dict) -> dict:
    """A histogram's snapshot: ``series`` maps label values to the list
    of seconds observed."""
    def cell(obs):
        counts = [0] * (len(BOUNDS) + 1)
        for s in obs:
            counts[next((i for i, b in enumerate(BOUNDS) if s <= b),
                        len(BOUNDS))] += 1
        return {"counts": counts, "sum": sum(obs), "count": len(obs)}
    return {"type": "histogram", "labelnames": list(labels),
            "buckets": BOUNDS,
            "cells": [[list(k), cell(v)] for k, v in sorted(series.items())]}


def _counter(series: dict) -> dict:
    return {"type": "counter", "labelnames": ["generation"],
            "cells": [[[g], v] for g, v in sorted(series.items())]}


def _snap(idle=None, loop=None, gc=(0.0, 0)) -> dict:
    out = {LOOP: _hist(("model", "phase"), loop or {}),
           GC_S: _counter({"0": 0.001, "2": gc[0]}),
           GC_N: _counter({"0": 50, "2": gc[1]})}
    if idle is not None:
        out[IDLE] = _hist(("model", "cause"), idle)
    return out


ARGS = {"histogram": IDLE, "split_by": "cause", "stall_s": 0.1,
        "sum_of": [{"cause": "admit"}, {"cause": "loop"}],
        "stalls_of": {LOOP: {"by": "phase", "but": ["wait"]}}}
BEFORE = _snap(idle={("lm", "wait"): [2.0], ("lm", "admit"): [0.004]},
               loop={("lm", "fetch"): [0.01] * 5})
AFTER = _snap(idle={("lm", "wait"): [2.0, 0.5, 1.5],
                    ("lm", "admit"): [0.004] * 11 + [0.2],
                    ("lm", "loop"): [0.01, 0.05],
                    ("other", "loop"): [0.04]},
              loop={("lm", "fetch"): [0.01] * 105,
                    ("lm", "admit"): [0.3, 0.02],
                    ("lm", "wait"): [0.1001] * 9},
              gc=(0.25, 3))


def _read(args, before, after, seconds=10.0, name="m"):
    reader = cells.load_module("readers", "hist_share")
    return reader.read({"name": name, "args": args},
                       {"window": {"before": before, "after": after,
                                   "seconds": seconds}})


@pytest.mark.parametrize("args, before, after, want", [
    # admit and loop of every model, the wait left out of the share
    (ARGS, BEFORE, AFTER, 100 * (0.04 + 0.2 + 0.06 + 0.04) / 10.0),
    # a selection by two labels
    (dict(ARGS, sum_of=[{"cause": "loop", "model": "lm"}]), BEFORE, AFTER,
     100 * 0.06 / 10.0),
    # the histogram is there and the window gained nothing: never starved
    (ARGS, AFTER, AFTER, 0.0),
    # registered and never observed (a batcher that has only started)
    (ARGS, _snap(idle={}), _snap(idle={}), 0.0),
    # a program without the histogram (the parent commit)
    (ARGS, _snap(), _snap(), None),
], ids=["share", "two_labels", "empty_window", "no_cells", "absent"])
def test_hist_share_reads_a_share_of_the_window(args, before, after, want):
    got = _read(args, before, after)
    assert got == (None if want is None else pytest.approx(want))


def test_hist_share_says_the_split_and_what_a_stall_coincided_with(capsys):
    _read(ARGS, BEFORE, AFTER, name="starved")
    split, stalls = capsys.readouterr().out.splitlines()
    assert split.startswith(f"starved: {IDLE} by cause over the window's "
                            "10.00 s: ")
    said = json.loads(split.split("s: ", 1)[1].rsplit("; all of them", 1)[0])
    assert said == {
        "admit": {"s": 0.24, "n": 11, "ms_each": pytest.approx(21.8182)},
        "loop": {"s": 0.1, "n": 3, "ms_each": pytest.approx(33.3333)},
        "wait": {"s": 2.0, "n": 2, "ms_each": 1000.0}}
    assert split.endswith("; all of them 23.400% of the window")
    assert stalls.startswith("starved: observations over 0.1 s: ")
    long, gc = stalls.split(": ", 2)[2].split(
        "; garbage collection in the window: ")
    long, spans = long.split("; seconds all of it gained between the two "
                             "snapshots: ")
    # the loop's phases gained 1.0 + 0.32 + 0.9009 s: the real span
    assert json.loads(spans) == {LOOP: 2.221}
    assert json.loads(long) == {
        IDLE + "{cause}": {"admit": 1, "wait": 2},
        LOOP + "{phase}": {"admit": 1}}     # the wait slices are left out
    assert json.loads(gc) == {"seconds": 0.25, "collections": 3.0,
                              "seconds_gen2": 0.25, "collections_gen2": 3.0}


def test_the_parent_program_is_silent_and_fails_nothing(capsys):
    assert _read(ARGS, _snap(), _snap()) is None
    assert capsys.readouterr().out == ""
    # and hist_mean, which reads admit_idle_ms.*, finds nothing either
    m = cells.load_json("layer_metrics", "admit_idle_ms.closed.json")
    got = cells.load_module("readers", m["reader"]).read(
        m, {"window": {"before": _snap(), "after": _snap(),
                       "seconds": 10.0}})
    assert got is None


@pytest.mark.parametrize("name, suffix", [
    ("gpt2_xl.batch_closed16", "closed"), ("gpt2_xl.chat_steady", "chat")])
def test_the_four_metrics_read_a_tiny_window(name, suffix, tmp_path, capsys):
    cell = tiny.serve_cell(name, tmp_path)
    run.attach(cell)
    outcome = cells.load_module("drivers", cell.workload["driver"]).run(cell)
    mine = {"device_starved_pct." + suffix, "admit_idle_ms." + suffix}
    theirs = {"device_starved_pct.", "admit_idle_ms."}
    listed = {m["name"] for m in cells.layer_metrics_for(cell.workload)
              if m["name"].startswith(tuple(theirs))}
    assert listed == mine           # and not the other traffic's pair
    for metric in sorted(mine):
        m = cells.load_json("layer_metrics", metric + ".json")
        value = cells.load_module("readers", m["reader"]).read(
            m, {"window": outcome["window"]})
        assert value is not None and 0.0 <= value < 1e3, (metric, value)
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("device_starved_pct." + suffix)]
    assert len(said) == 2 and '"admit"' in said[0]
