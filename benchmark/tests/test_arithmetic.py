"""Percentile, lateness, interval and histogram arithmetic, the peaks
table, and the seeded schedules."""
import math
import os
import sys

import pytest

import peaks
from harness import counters, stats

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen"))
import schedule

CHAT = {"arrivals": {"kind": "poisson", "rate": 4.0}, "ramp_s": 5,
        "prompt_len": {"median": 128, "sigma": 0.8, "lo": 16, "hi": 512},
        "output_len": {"median": 32, "sigma": 0.6, "lo": 8, "hi": 128}}


def test_percentile_is_nearest_rank_and_keeps_misses():
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([5.0], 95) == 5.0
    # one miss in ten is above the 90th percentile, not below it
    assert stats.percentile([1.0] * 9 + [math.inf], 90) == 1.0
    assert stats.percentile([1.0] * 9 + [math.inf], 95) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_lateness_counts_from_the_due_instant():
    due, sent, first = [0.0, 1.0, 2.0], [0.001, 1.5, 2.0], [0.2, 1.9, 2.3]
    late = [s - d for s, d in zip(sent, due)]
    ttft = [f - d for f, d in zip(first, due)]
    assert stats.percentile(late, 95) == pytest.approx(0.5)
    assert max(ttft) == pytest.approx(0.9)   # the stall is in the TTFT


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert stats.union_seconds(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_iqr_spread_is_of_the_median():
    assert stats.iqr_spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_spread([9, 10, 10, 10, 10, 11]) \
        == pytest.approx(0.025, abs=0.05)


def test_hist_delta_and_quantile():
    def snap(counts, total):
        return {"h": {"type": "histogram", "labelnames": ["model"],
                      "buckets": [0.1, 1.0],
                      "cells": [[["lm"], {"counts": counts, "sum": total,
                                          "count": sum(counts)}]]}}
    d = counters.hist_delta(snap([1, 0, 0], 0.05), snap([1, 10, 0], 5.05),
                            "h", model="lm")
    assert d["count"] == 10 and d["sum"] == pytest.approx(5.0)
    assert counters.hist_quantile(d, 0.5) == pytest.approx(0.55)
    assert counters.hist_delta(snap([1, 0, 0], 0.05), snap([1, 0, 0], 0.05),
                               "h") is None
    assert counters.hist_delta({}, {}, "absent") is None


def test_peaks_table_refuses_unknown_kinds_and_shares_over_100():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.share_pct(50.0, 200.0, "x") == 25.0
    with pytest.raises(ValueError):
        peaks.share_pct(201.0, 200.0, "x")


def test_schedule_repeats_for_one_seed_and_differs_for_two():
    a = schedule.build(CHAT, 50257, 2 ** 31 + 5, 20)
    b = schedule.build(CHAT, 50257, 2 ** 31 + 5, 20)
    c = schedule.build(CHAT, 50257, 6, 20)
    assert a == b
    assert a["requests"][0]["tokens"] != c["requests"][0]["tokens"]
    # the same work in another order: same lengths, same gaps
    for key in ("prompt_len", "max_new"):
        assert sorted(r[key] for r in a["requests"]) \
            == sorted(r[key] for r in c["requests"])
    dues = [r["due"] for r in a["requests"]]
    assert dues == sorted(dues) and dues[0] == 0.0
    n = len(dues)
    assert n == 100 and dues[-1] < 25.0
    assert all(16 <= r["prompt_len"] <= 512 and 8 <= r["max_new"] <= 128
               and len(r["tokens"]) == r["prompt_len"]
               for r in a["requests"])


def test_closed_schedule_deals_requests_to_clients():
    closed = dict(CHAT, arrivals={"kind": "closed", "clients": 4,
                                  "per_client": 3})
    s = schedule.build(closed, 100, 1, 5)
    assert len(s["requests"]) == 12
    assert sorted({r["client"] for r in s["requests"]}) == [0, 1, 2, 3]
