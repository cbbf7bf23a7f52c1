"""The reader ``hist_mean`` against a synthetic pair of snapshots (label
selection, all series, a count of zero, a histogram the program lacks),
and the five per-layer metrics of PR 24 against the window of a tiny run
of each driver on the CPU (the counters are there without a trace)."""
import pytest

import run
import tiny
from harness import cells

LOOP = "dl4j_tpu_serving_loop_phase_seconds"
PREFILL = "dl4j_tpu_serving_prefill_seconds"


def _snap(loop: dict, prefill=(0, 0.0)) -> dict:
    """A registry snapshot: ``loop`` maps (model, phase) to (count, sum)."""
    cell = lambda n, s: {"counts": [n, 0, 0], "sum": s, "count": n}
    return {
        LOOP: {"type": "histogram", "labelnames": ["model", "phase"],
               "buckets": [0.1, 1.0],
               "cells": [[[m, p], cell(n, s)]
                         for (m, p), (n, s) in sorted(loop.items())]},
        PREFILL: {"type": "histogram", "labelnames": ["model"],
                  "buckets": [0.1, 1.0],
                  "cells": [[["lm"], cell(*prefill)]]}}


BEFORE = _snap({("lm", "fetch"): (10, 0.5), ("lm", "grow"): (10, 0.01),
                ("lm", "emit"): (10, 0.02), ("lm", "wait"): (3, 0.3)},
               prefill=(2, 0.06))
AFTER = _snap({("lm", "fetch"): (110, 5.5), ("lm", "grow"): (110, 0.11),
               ("lm", "emit"): (110, 0.32), ("lm", "wait"): (3, 0.3),
               ("lm", "admit"): (100, 0.2),
               ("other", "fetch"): (7, 7.0), ("other", "grow"): (7, 7.0)},
              prefill=(6, 0.18))
GAP = {"histogram": LOOP, "count_of": {"phase": "fetch", "model": "lm"},
       "sum_of": [{"phase": "grow", "model": "lm"},
                  {"phase": "emit", "model": "lm"},
                  {"phase": "upload", "model": "lm"}]}


@pytest.mark.parametrize("args, before, after, want", [
    # two phases summed (a third the window never saw adds nothing), over
    # the count of a third; another model's series are left out
    (GAP, BEFORE, AFTER, 1e3 * (0.10 + 0.30) / 100),
    # no labels: every series of the histogram
    ({"histogram": PREFILL, "sum_of": [{}], "count_of": {}},
     BEFORE, AFTER, 1e3 * 0.12 / 4),
    # the series exists and gained nothing
    (dict(GAP, count_of={"phase": "wait"}), BEFORE, AFTER, None),
    # the window observed nothing at all
    (GAP, AFTER, AFTER, None),
    # a program without the histogram (the parent commit)
    (dict(GAP, histogram="dl4j_tpu_no_such_seconds"), BEFORE, AFTER, None),
], ids=["labels", "all_series", "zero_count", "empty_window", "absent"])
def test_hist_mean_reads_sums_over_a_count(args, before, after, want):
    reader = cells.load_module("readers", "hist_mean")
    got = reader.read({"name": "m", "args": args},
                      {"window": {"before": before, "after": after,
                                  "seconds": 10.0}})
    assert got == (None if want is None else pytest.approx(want))


def test_hist_mean_says_the_split_the_share_and_the_period(capsys):
    reader = cells.load_module("readers", "hist_mean")
    args = dict(GAP, count_of={"phase": "fetch"}, split_by="phase")
    reader.read({"name": "gap", "args": args},
                {"window": {"before": _snap({}), "after": _snap(
                    {("lm", "fetch"): (100, 8.0), ("lm", "emit"): (100, 1.0),
                     ("lm", "wait"): (5, 0.5)}), "seconds": 10.0}})
    line, = capsys.readouterr().out.splitlines()
    assert line.startswith("gap: " + LOOP + " by phase")
    assert '"emit": 10.0, "fetch": 80.0, "wait": 5.0' in line
    assert "95.00% of the window's 10.00 s; period 100.000 ms" in line


def _read(name, outcome):
    m = cells.load_json("layer_metrics", name + ".json")
    return cells.load_module("readers", m["reader"]).read(
        m, {"window": outcome["window"]})


@pytest.mark.parametrize("name, metrics", [
    ("gpt2_xl.batch_closed16", ["decode_host_gap_ms.batch"]),
    ("gpt2_xl.chat_steady", ["decode_host_gap_ms.chat",
                             "admit_host_ms.chat"]),
])
def test_serving_metrics_read_a_tiny_window(name, metrics, tmp_path, capsys):
    cell = tiny.serve_cell(name, tmp_path)
    run.attach(cell)
    outcome = cells.load_module("drivers", cell.workload["driver"]).run(cell)
    assert {m["name"] for m in cells.layer_metrics_for(cell.workload)} \
        >= set(metrics)
    for metric in metrics:
        value = _read(metric, outcome)
        assert value is not None and 0.0 < value < 1e3, (metric, value)
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("decode_host_gap_ms")]
    assert len(said) == 1 and '"fetch"' in said[0] and '"wait"' in said[0]


@pytest.mark.parametrize("name", ["resnet50.train_b256",
                                  "resnet50.mesh4_b1024"])
def test_training_metrics_read_a_tiny_window(name):
    cell = tiny.train_cell(name)
    run.attach(cell)
    outcome = cells.load_module("drivers", cell.workload["driver"]).run(cell)
    assert {m["name"] for m in cells.layer_metrics_for(cell.workload)} \
        >= {"h2d_ms.train", "step_enqueue_ms.train"}
    for metric in ("h2d_ms.train", "step_enqueue_ms.train"):
        value = _read(metric, outcome)
        assert value is not None and 0.0 < value < 1e4, (metric, value)
    # every step of the window observed both, once
    w = outcome["window"]
    for hist in ("dl4j_tpu_step_h2d_seconds",
                 "dl4j_tpu_step_compute_seconds"):
        gained = w["after"][hist]["cells"][0][1]["count"] \
            - w["before"][hist]["cells"][0][1]["count"]
        assert gained == w["steps"]
