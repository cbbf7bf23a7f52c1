"""The reader ``counter_ratio`` against synthetic pairs of snapshots: what
the selected counters gained over what another gained, a numerator that
gained nothing (0), a denominator that gained nothing and a program
without the counters (the parent commit: nothing to read, nothing said),
the line it says; and the five per-layer metrics of
PR 50 against the window of a tiny run of each serving driver on the CPU
(the counters are there without a trace)."""

import pytest

import run
import tiny
from harness import cells

STREAM_S = "dl4j_tpu_serving_stream_token_seconds_total"
STREAM_N = "dl4j_tpu_serving_stream_tokens_delivered_total"


def _counter(labels, series: dict) -> dict:
    return {"type": "counter", "labelnames": list(labels),
            "cells": [[list(k), v] for k, v in sorted(series.items())]}


def _snap(queued=None, write=0.0, tokens=None) -> dict:
    out = {}
    if queued is not None:
        out[STREAM_S] = _counter(("model", "stage"), {
            ("lm", "queued"): queued, ("lm", "write"): write})
    if tokens is not None:
        out[STREAM_N] = _counter(("model",), {("lm",): tokens})
    return out


QUEUED = {"sum_of": [{"name": STREAM_S, "labels": {"stage": "queued"}}],
          "over": {"name": STREAM_N, "labels": {}}, "scale": 1000.0}
BOTH = dict(QUEUED, sum_of=QUEUED["sum_of"]
            + [{"name": STREAM_S, "labels": {"stage": "write"}}])
BEFORE = _snap(queued=1.0, write=0.5, tokens=1000)
AFTER = _snap(queued=4.0, write=1.0, tokens=3000)


def _read(args, before, after, name="m"):
    reader = cells.load_module("readers", "counter_ratio")
    return reader.read({"name": name, "args": args},
                       {"window": {"before": before, "after": after,
                                   "seconds": 10.0}})


@pytest.mark.parametrize("args, before, after, want", [
    # 3 s over 2,000 tokens, in ms a token
    (QUEUED, BEFORE, AFTER, 1.5),
    # two selections summed
    (BOTH, BEFORE, AFTER, 1.75),
    # no scale: the plain ratio
    ({k: v for k, v in QUEUED.items() if k != "scale"}, BEFORE, AFTER,
     0.0015),
    # a counter that was not there before the window counts from nothing
    (QUEUED, _snap(), AFTER, 4000.0 / 3000),
    # the numerator is there and gained nothing
    (QUEUED, dict(BEFORE, **{STREAM_S: AFTER[STREAM_S]}), AFTER, 0.0),
    # the denominator gained nothing: nothing to divide by
    (QUEUED, AFTER, AFTER, None),
    # a program without the counters (the parent commit), either of them
    (QUEUED, _snap(tokens=1), _snap(tokens=9), None),
    (QUEUED, _snap(queued=1.0), _snap(queued=2.0), None),
], ids=["ratio", "two_summed", "no_scale", "new_in_window",
        "numerator_still", "denominator_still", "no_numerator",
        "no_denominator"])
def test_counter_ratio_reads_what_gained_over_what(args, before, after,
                                                   want, capsys):
    got = _read(args, before, after)
    assert got == (None if want is None else pytest.approx(want))
    # one line with what both gained; silent where there is nothing
    assert len(capsys.readouterr().out.splitlines()) == (want is not None)


NEW = {"closed": ["loop_offcpu_ms", "stream_queued_ms", "stream_write_ms"],
       "chat": ["loop_offcpu_ms", "stream_queued_ms"]}


@pytest.mark.parametrize("name, suffix", [
    ("gpt2_xl.batch_closed16", "closed"), ("gpt2_xl.chat_steady", "chat")])
def test_the_five_metrics_read_a_tiny_window(name, suffix, tmp_path,
                                              capsys):
    cell = tiny.serve_cell(name, tmp_path)
    run.attach(cell)
    outcome = cells.load_module("drivers", cell.workload["driver"]).run(cell)
    mine = {f"{m}.{suffix}" for m in NEW[suffix]}
    listed = {m["name"] for m in cells.layer_metrics_for(cell.workload)}
    other = {f"{m}.{s}" for s in NEW if s != suffix for m in NEW[s]}
    assert mine <= listed and not other & listed
    for metric in sorted(mine):
        m = cells.load_json("layer_metrics", metric + ".json")
        value = cells.load_module("readers", m["reader"]).read(
            m, {"window": outcome["window"]})
        assert value is not None and 0.0 <= value < 1e4, (metric, value)
    if suffix == "closed":
        # the loop's off-CPU time a step is part of its wall time a step
        read = lambda n: cells.load_module("readers", "hist_mean").read(
            cells.load_json("layer_metrics", n + ".json"),
            {"window": outcome["window"]})
        assert read("loop_offcpu_ms.closed") <= \
            read("decode_host_gap_ms.batch")
        # what the consumers took is what the load generator received,
        # but for the tokens in flight at the two snapshots
        w = outcome["window"]
        took = sum(c for _k, c in w["after"][STREAM_N]["cells"]) \
            - sum(c for _k, c in w["before"].get(
                STREAM_N, {"cells": []})["cells"])
        assert took > 0
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("loop_offcpu_ms.")]
    assert any('"bookkeep"' in ln for ln in said)
