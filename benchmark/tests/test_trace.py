"""The trace reduction against the small ``.xplane.pb`` recorded on the
chip (``record_fixture.py``, PR 23: three runs of ``jit_step``, a pause of
2 ms, one run of ``jit_other``), and against made-up events for what a
one-chip fixture cannot hold (collectives, several chips)."""
import os

import pytest

from harness import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(trace.Trace(FIXTURE), chips=1)


def test_fixture_modules_and_busy_time(reduced):
    m = reduced["modules"]
    assert m["jit_step"]["count"] == 3 and m["jit_other"]["count"] == 1
    assert 2e-6 < m["jit_step"]["total_s"] / 3 < 5e-6
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] == reduced["busy_s_device0"]
    assert reduced["collective_s"] == 0.0
    # the device sat idle nearly all of these few milliseconds
    assert 1 - reduced["busy_s"] / reduced["window_s"] > 0.99


def test_fixture_top_ops_and_gaps(reduced):
    names = [n for n, _s in reduced["device_ops"]]
    assert names[0] == "convolution_tanh_fusion"
    assert all(" = " not in n and not n.startswith("%") for n in names)
    secs = [s for _n, s in reduced["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    gaps = reduced["idle_gaps"]
    assert 1 <= len(gaps) <= 5
    assert gaps[0][1] == max(s for _n, s in gaps) > 2e-3
    assert {n for n, _s in gaps} <= {"bench.pause", "bench.window",
                                     "bench.enqueue_step", "unattributed"}


def _made_up(devices, host=()):
    t = trace.Trace.__new__(trace.Trace)
    t.devices, t.host, t.plane_names = devices, list(host), []
    return t


def test_collectives_idle_and_chip_average_on_made_up_events():
    dev0 = {"ops": [("%fusion.1 = f32[] fusion()", 0.0, 1.0),
                    ("%all-reduce.3 = f32[] all-reduce()", 1.0, 1.5),
                    ("%all-gather-start = f32[] x()", 1.2, 1.4),
                    ("%fusion.1 = f32[] fusion()", 3.0, 4.0)],
            "modules": [("jit_step(1)", 0.0, 1.5), ("jit_step(1)", 3.0, 4.0)]}
    dev1 = {"ops": [("%fusion.1 = f32[] fusion()", 0.0, 4.0)], "modules": []}
    r = trace.reduce_trace(
        _made_up({0: dev0, 1: dev1},
                 [("bench.window", 0.0, 4.0), ("bench.sleep", 1.6, 2.9)]),
        chips=2)
    assert r["window_s"] == pytest.approx(4.0)
    assert r["busy_s_device0"] == pytest.approx(2.5)
    assert r["busy_s"] == pytest.approx((2.5 + 4.0) / 2)
    assert r["collective_s"] == pytest.approx(0.7)
    assert r["modules"]["jit_step"] == {"count": 2,
                                        "total_s": pytest.approx(2.5)}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert r["idle_gaps"] == [["bench.sleep", pytest.approx(1.5)]]


def test_a_trace_without_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_trace(_made_up({}), chips=1)
    with pytest.raises(ValueError):
        trace.reduce_trace(_made_up({0: {"ops": [], "modules": []}}),
                           chips=1)
