"""Phase spans on the profiler's clock (ISSUE 24).

One seam (``Tracer.span``): the Chrome-trace event, the profiler
annotation ``dl4j.<name>`` and the phase's histogram observation come
from the same two clock reads.  Covers: the program's spans inside a
capture started by ``jax.profiler.start_trace`` directly (no wrapper of
the program's), the decode loop's phases (one observation each a step,
together covering the loop thread's time), the idle loop, ``h2d``
observed in every fit path, the Chrome trace's nesting, and a phase left
by an exception.

The drain clock (ISSUE 36): the loop thread books every stretch the device
stood idle to its cause at the dispatch that ends it
(``dl4j_tpu_serving_device_idle_seconds{model, cause}``, the Chrome event
``serving.device.idle``), a stall leaves ``serving.loop.stall``, and the
collector's pauses are two process counters.
"""
import gc
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.fault import injection
from deeplearning4j_tpu.remote import scheduler
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nlp.transformer import TransformerLM
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
from deeplearning4j_tpu.remote import ContinuousBatcher
from deeplearning4j_tpu.telemetry import (SERVING_LOOP_PHASES,
                                          MetricsRegistry, RequestContext,
                                          Tracer, etl_fetch, get_registry,
                                          request_context, set_tracer,
                                          tracer)

pytestmark = pytest.mark.telemetry

STEP_PHASES = ("grow", "upload", "dispatch", "fetch", "emit", "bookkeep")
LOOP_HIST = "dl4j_tpu_serving_loop_phase_seconds"
IDLE_HIST = "dl4j_tpu_serving_device_idle_seconds"


@pytest.fixture(autouse=True)
def fresh_telemetry():
    prev_reg = telemetry.set_registry(MetricsRegistry())
    prev_tr = set_tracer(Tracer())
    yield
    set_tracer(prev_tr)
    telemetry.set_registry(prev_reg)


def _lm(layers=1, seed=5, vocab=40, heads=2, headSize=8):
    return TransformerLM(vocabSize=vocab, nLayers=layers, nHeads=heads,
                         headSize=headSize, maxLen=64, seed=seed)


def _mlp():
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(0.01))
            .list()
            .layer(DenseLayer.builder().nIn(8).nOut(16)
                   .activation("relu").build())
            .layer(OutputLayer.builder("mcxent").nOut(4)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(8)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def _ds(n=16, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, n)]
    return DataSet(x, y)


def _cells(hist, model, by):
    """{value of label ``by``: (count, sum)} of ``hist`` for ``model``."""
    h = get_registry().get(hist)
    if h is None:
        return {}
    d = h.data()
    out = {}
    for key, cell in d["cells"]:
        lab = dict(zip(d["labelnames"], key))
        if lab["model"] == model:
            out[lab[by]] = (cell["count"], cell["sum"])
    return out


def _phase_cells(model):
    """{phase: (count, sum)} of the loop-phase histogram for ``model``."""
    return _cells(LOOP_HIST, model, "phase")


def _idle_cells(model):
    """{cause: (count, sum)} of the device-idle histogram for ``model``."""
    return _cells(IDLE_HIST, model, "cause")


def _generate(cb, quota, prompt=(1, 2, 3)):
    ctx = RequestContext.new()
    with request_context(ctx):
        toks = [t for t in cb.submitStream(
            {"tokens": list(prompt), "maxNewTokens": quota})
            if isinstance(t, int)]
    assert len(toks) == quota
    return ctx


def _host_lines(log_dir):
    """[{annotation name: [(start_ns, end_ns), ...]}] per thread of the
    capture's host plane."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, "the profiler wrote no capture"
    lines = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            names = {}
            for e in ln.events:
                if e.name.startswith("dl4j."):
                    names.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
            if names:
                lines.append(names)
    return lines


# ----------------------------- a capture nobody told the program about --

@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One profiler session started through ``jax.profiler`` itself; the
    loop thread serves a request, the main thread fits two batches and
    leaves one span by an exception, then enters a sibling."""
    prev_reg = telemetry.set_registry(MetricsRegistry())
    prev_tr = set_tracer(Tracer())
    log_dir = str(tmp_path_factory.mktemp("capture"))
    cb = ContinuousBatcher(_lm(), name="cap", maxSlots=2,
                           pageSize=8).start()
    net = _mlp()
    net.fit(_ds())                  # compile outside the capture
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            _generate(cb, 4)
            net.fit(_ds(seed=1))
            net.fit(ListDataSetIterator([_ds(seed=2)], batch=16))
            with pytest.raises(KeyError):
                with tracer().span("boom"):
                    raise KeyError("left by an exception")
            with tracer().span("after_boom"):
                time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
    finally:
        cb.shutdown()
        set_tracer(prev_tr)
        telemetry.set_registry(prev_reg)
    return _host_lines(log_dir)


def test_capture_holds_the_loop_threads_spans(capture):
    want = {"dl4j.serving.decode.step", "dl4j.serving.loop.fetch",
            "dl4j.serving.prefill", "dl4j.serving.loop.admit",
            "dl4j.serving.loop.iteration"}
    holders = [ln for ln in capture if "dl4j.serving.loop.fetch" in ln]
    assert len(holders) == 1, "the loop's phases sit on ONE thread's line"
    loop = holders[0]
    assert want <= set(loop), sorted(loop)
    # 4 tokens = 1 from the prefill + 3 decode steps, each with a fetch;
    # the loop reads a step one iteration after it dispatched it, so the
    # last is read in an iteration of its own
    assert len(loop["dl4j.serving.loop.fetch"]) == 3
    assert len(loop["dl4j.serving.loop.dispatch"]) == 3
    assert len(loop["dl4j.serving.decode.step"]) == 4
    assert len(loop["dl4j.serving.prefill"]) == 1
    # and the training spans are NOT on the loop thread's line
    assert "dl4j.step" not in loop and "dl4j.h2d" not in loop


def test_capture_holds_the_fit_paths_spans(capture):
    holders = [ln for ln in capture if "dl4j.step" in ln]
    assert len(holders) == 1
    main = holders[0]
    assert len(main["dl4j.step"]) == 2 and len(main["dl4j.h2d"]) == 2
    assert len(main["dl4j.etl"]) == 1       # the iterator-driven fit
    # phases of one step do not overlap: h2d ends before its step starts
    for (_h0, h1), (s0, _s1) in zip(sorted(main["dl4j.h2d"]),
                                    sorted(main["dl4j.step"])):
        assert h1 <= s0


def test_capture_closes_a_span_left_by_an_exception(capture):
    main = next(ln for ln in capture if "dl4j.boom" in ln)
    (b0, b1), = main["dl4j.boom"]
    (a0, _a1), = main["dl4j.after_boom"]
    assert b0 <= b1 <= a0, "the annotation was left open past its body"


# ----------------------------------------------- the decode loop's phases --

def test_every_phase_once_a_step_and_the_loop_is_covered():
    quota = 25
    # wide enough that a step takes milliseconds on the CPU: what lies
    # between two phases (a span's own bookkeeping) is some 20 us
    cb = ContinuousBatcher(_lm(layers=4, vocab=512, heads=4, headSize=32),
                           name="ph", maxSlots=2, pageSize=8).start()
    try:
        _generate(cb, quota)
        # the stream has ended, so its last step was read: the loop goes
        # idle with nothing unread on the device
        assert cb._inflight is None
    finally:
        cb.shutdown()
    count = lambda name: int(get_registry().get(name).value(model="ph"))
    steps = count("dl4j_tpu_serving_decode_steps_total")
    ahead = count("dl4j_tpu_serving_decode_steps_overlapped_total")
    assert count("dl4j_tpu_serving_decode_tokens_discarded_total") == 0
    cells = _phase_cells("ph")
    # one stream that never waits: every step but the first was
    # dispatched while the one before it was unread, and the last is
    # read by an iteration that grows nothing and dispatches nothing
    assert steps == quota - 1 and ahead == steps - 1
    grows = steps + 1
    for p in STEP_PHASES:
        want = grows if p == "grow" else steps
        assert cells[p][0] == want, (p, cells[p], want)
    assert cells["admit"][0] >= grows       # once an iteration
    # the loop thread's wall time, off the Chrome trace: first admit to
    # the end of the last bookkeep; the phases are siblings on one thread
    evs = [e for e in tracer().events()
           if e["name"].startswith("serving.loop.") and e["ph"] == "X"
           and e["name"] not in ("serving.loop.wait",
                                 "serving.loop.iteration")]
    assert len({e["tid"] for e in evs}) == 1
    t0 = min(e["ts"] for e in evs if e["name"] == "serving.loop.admit")
    t1 = max(e["ts"] + e["dur"] for e in evs)
    inside = [e for e in evs if e["ts"] >= t0]
    covered = sum(e["dur"] for e in inside)
    wall = t1 - t0
    assert covered <= wall * (1 + 1e-9)
    assert covered >= 0.90 * wall, (covered, wall)
    # and what lies between two phases is inside the iteration's own span
    its = [e for e in tracer().events()
           if e["name"] == "serving.loop.iteration"]
    assert len(its) == cells["admit"][0]
    assert all(any(i["ts"] <= e["ts"] and
                   e["ts"] + e["dur"] <= i["ts"] + i["dur"] for i in its)
               for e in evs)
    # span and histogram are the same two clock reads: they agree
    by_name = {}
    for e in evs:
        by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur"] * 1e-6
    for p in STEP_PHASES + ("admit",):
        assert by_name["serving.loop." + p] == \
            pytest.approx(cells[p][1], rel=1e-6, abs=1e-9)


def test_an_idle_batcher_accrues_wait_and_nothing_else():
    t0 = time.perf_counter()
    cb = ContinuousBatcher(_lm(), name="idle", maxSlots=2,
                           pageSize=8).start()
    try:
        time.sleep(0.35)
    finally:
        cb.shutdown()
    elapsed = time.perf_counter() - t0
    cells = _phase_cells("idle")
    assert set(cells) == {"wait"}, cells
    count, total = cells["wait"]
    assert count >= 3                       # slices of at most 0.1 s
    assert 0.25 <= total <= elapsed
    evs = tracer().events()
    assert {e["name"] for e in evs} == {"serving.loop.wait"}
    assert sum(e["dur"] for e in evs) * 1e-6 == pytest.approx(total)
    assert max(e["dur"] for e in evs) * 1e-6 < 0.2      # slices, not one


# ------------------------------------------------------ the drain clock --

def _batcher(name, slots=2):
    return ContinuousBatcher(_lm(), name=name, maxSlots=slots,
                             pageSize=8).start()


def _idle_events(cause=None):
    return sorted((e for e in tracer().events()
                   if e["name"] == "serving.device.idle"
                   and cause in (None, e["args"]["cause"])),
                  key=lambda e: e["ts"])


def _spans(name):
    return sorted((e for e in tracer().events() if e["name"] == name),
                  key=lambda e: e["ts"])


def _stalls(prefix=""):
    """The ``serving.loop.stall`` instants whose phase starts so (under
    load a real phase may take its 0.1 s too)."""
    return [e for e in _spans("serving.loop.stall")
            if e["args"]["phase"].startswith(prefix)]


@pytest.fixture
def every_stretch_an_event(monkeypatch):
    """On the CPU an admission's gap is under the millisecond from which
    a stretch is also a Chrome event: keep them all."""
    monkeypatch.setattr(scheduler, "_IDLE_EVENT_SECONDS", 0.0)


@pytest.fixture
def slowdown():
    yield injection.set_replica_slowdown
    injection.clear_serving_faults()


def test_an_idle_stretch_before_a_request_is_booked_to_wait():
    t0 = time.perf_counter()
    cb = _batcher("w")
    try:
        time.sleep(0.3)
        # one token: the prefill is the only dispatch, and the stretch it
        # ends is the sleep, through which the loop waited
        _generate(cb, 1)
    finally:
        cb.shutdown()
    elapsed = time.perf_counter() - t0
    cells = _idle_cells("w")
    assert set(cells) == {"wait"}, cells
    count, total = cells["wait"]
    assert count == 1 and 0.3 <= total <= elapsed
    ev, = _idle_events()
    assert ev["args"] == {"replica": "w", "cause": "wait", "bound": False}
    assert ev["dur"] * 1e-6 == pytest.approx(total)
    # on the loop thread's track, over the wait slices it slept through,
    # up to the prefill's dispatch
    waits = _spans("serving.loop.wait")
    prefill, = _spans("serving.prefill")
    assert ev["tid"] == prefill["tid"] == waits[0]["tid"]
    inside = [w for w in waits if ev["ts"] <= w["ts"]
              and w["ts"] + w["dur"] <= ev["ts"] + ev["dur"]]
    assert len(inside) >= 3
    assert prefill["ts"] <= ev["ts"] + ev["dur"] \
        <= prefill["ts"] + prefill["dur"]
    # a pause of the traffic is not a stall of the program
    assert not _stalls("device.idle.")


def test_an_admission_books_one_stretch_to_admit(every_stretch_an_event):
    cb = _batcher("a")
    per_client, quota = 3, 6

    def client():
        for _ in range(per_client):
            _generate(cb, quota)

    try:
        clients = [threading.Thread(target=client) for _ in range(4)]
        for c in clients:
            c.start()
        for c in clients:
            c.join()
    finally:
        cb.shutdown()
    admitted = int(get_registry().get(
        "dl4j_tpu_serving_sequences_admitted_total").value(model="a"))
    assert admitted == 4 * per_client
    cells = _idle_cells("a")
    # every admission is followed by a dispatch (a step for its second
    # token, or the next admission's prefill), which ends its stretch
    assert cells["admit"][0] == admitted
    stretches = _idle_events("admit")
    assert len(stretches) == admitted
    assert sum(e["dur"] for e in stretches) * 1e-6 == \
        pytest.approx(cells["admit"][1])
    assert all(e["args"]["bound"] is False for e in stretches)
    # each opens inside an admit phase (at its first-token read) and ends
    # inside that phase (the next admission's prefill) or the dispatch
    # phase that follows it
    admits = _spans("serving.loop.admit")
    dispatches = _spans("serving.loop.dispatch")
    for e in stretches:
        opened = [a for a in admits
                  if a["ts"] <= e["ts"] <= a["ts"] + a["dur"]]
        assert len(opened) == 1
        closes = next(d["ts"] + d["dur"] for d in dispatches
                      if d["ts"] + d["dur"] >= e["ts"])
        assert e["ts"] + e["dur"] <= max(
            closes, opened[0]["ts"] + opened[0]["dur"])


def test_a_slow_loop_is_booked_to_loop_and_not_to_admit(slowdown):
    delay, quota = 0.03, 9
    cb = _batcher("s")
    try:
        slowdown("s", delay)
        t0 = time.perf_counter()
        _generate(cb, quota)
        elapsed = time.perf_counter() - t0
    finally:
        cb.shutdown()
    steps = int(get_registry().get(
        "dl4j_tpu_serving_decode_steps_total").value(model="s"))
    cells = _idle_cells("s")
    # the first step's dispatch ends the admission's stretch, which holds
    # one delay; every later one finds the device idle for a delay and
    # the loop's own work (is_ready() found it, and the stretch since
    # the dispatch before is a bound)
    assert cells["admit"][0] == 1 and cells["admit"][1] < 2 * delay + 0.1
    count, total = cells["loop"]
    assert steps - 1 <= count <= 2 * steps
    assert 0.7 * delay * (steps - 1) <= total <= elapsed
    bounds = {e["args"]["bound"] for e in _idle_events("loop")}
    assert bounds == {True}


def test_idle_stretches_are_disjoint_and_inside_the_wall_time(
        every_stretch_an_event, slowdown):
    cb = _batcher("d")
    try:
        _generate(cb, 5)
        time.sleep(0.15)
        slowdown("d", 0.005)
        _generate(cb, 5)
    finally:
        cb.shutdown()
    evs = _idle_events()
    assert {e["args"]["cause"] for e in evs} == {"wait", "admit", "loop"}
    for a, b in zip(evs, evs[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3     # us
    # all but the wait before the first request lie between the first
    # dispatch onto the device (its prefill) and the end of the last
    first = _spans("serving.prefill")[0]["ts"]
    last = max(e["ts"] + e["dur"] for e in _spans("serving.loop.dispatch"))
    assert evs[0]["args"]["cause"] == "wait"
    assert evs[0]["ts"] + evs[0]["dur"] >= first
    assert sum(e["dur"] for e in evs[1:]) <= last - first
    cells = _idle_cells("d")
    assert sum(n for n, _s in cells.values()) == len(evs)
    assert sum(s for _n, s in cells.values()) == pytest.approx(
        sum(e["dur"] for e in evs) * 1e-6)


def test_a_stall_leaves_one_instant_with_what_it_coincided_with(slowdown):
    cb = _batcher("st")
    try:
        time.sleep(0.15)                # a wait of 0.1 s or more: no stall
        slowdown("st", 0.12)
        _generate(cb, 3)
    finally:
        cb.shutdown()
    long = [e for e in _idle_events() if e["dur"] >= 0.1e6]
    assert [e["args"]["cause"] for e in long] == ["wait", "admit", "loop"]
    stalls = _stalls("device.idle.")
    assert [e["args"]["phase"] for e in stalls] == \
        ["device.idle.admit", "device.idle.loop"]
    for st, e in zip(stalls, long[1:]):
        assert st["ph"] == "i" and st["tid"] == e["tid"]
        assert set(st["args"]) == {"replica", "phase", "seconds",
                                   "gc_seconds", "threads", "queued"}
        assert st["args"]["seconds"] == pytest.approx(e["dur"] * 1e-6,
                                                      abs=1e-5)
        assert st["args"]["threads"] >= 2 and st["args"]["queued"] == 0
        assert 0.0 <= st["args"]["gc_seconds"] <= st["args"]["seconds"]
    # a loop phase of 0.1 s or more leaves one too; a wait slice does not
    seen = len(_stalls())
    cb._observePhase("emit", 0.2)
    cb._observePhase("wait", 0.2)
    assert [e["args"]["phase"] for e in _stalls()[seen:]] == ["emit"]


def test_a_collection_raises_both_gc_series():
    def read():
        reg = get_registry()
        return [reg.get("dl4j_tpu_process_gc_" + n).value(generation="2")
                for n in ("pause_seconds_total", "collections_total")]

    before = read()
    junk = [[i] for i in range(1000)]
    junk.append(junk)
    del junk
    t0 = time.perf_counter()
    gc.collect()
    elapsed = time.perf_counter() - t0
    after = read()
    assert after[1] == before[1] + 1
    assert 0.0 < after[0] - before[0] <= elapsed
    assert telemetry.gc_pause_seconds(t0) <= elapsed
    # the series belong to the process's registry, whichever it is
    fresh = MetricsRegistry()
    assert fresh.get("dl4j_tpu_process_gc_collections_total") is None
    prev = telemetry.set_registry(fresh)
    try:
        gc.collect()
        assert fresh.get("dl4j_tpu_process_gc_collections_total").value(
            generation="2") == 1
        assert read()[0] > 0.0
    finally:
        telemetry.set_registry(prev)
    assert read()[1] == after[1]


# ------------------------------------------------ h2d in every fit path --

def _fit_dataset(net, batches):
    for ds in batches:
        net.fit(ds)


def _fit_wrapper4(net, batches):
    pw = ParallelWrapper(net, mesh=DeviceMesh(
        data=4, devices=jax.devices()[:4]))
    for ds in batches:
        pw.fit(ListDataSetIterator([ds], batch=16))


@pytest.mark.parametrize("fit", [_fit_dataset, _fit_wrapper4],
                         ids=["fit_dataset", "parallel_wrapper4"])
def test_h2d_is_observed_once_a_step(fit):
    net = _mlp()
    fit(net, [_ds(seed=s) for s in range(3)])
    reg = get_registry()
    assert reg.get("dl4j_tpu_step_h2d_seconds").count() == 3
    assert reg.get("dl4j_tpu_step_compute_seconds").count() == 3
    h2d = [e for e in tracer().events() if e["name"] == "h2d"]
    assert len(h2d) == 3
    assert sum(e["dur"] for e in h2d) * 1e-6 == pytest.approx(
        reg.get("dl4j_tpu_step_h2d_seconds").sum(), rel=1e-6, abs=1e-9)


def test_etl_is_a_real_span_and_keeps_the_folded_wait():
    it = ListDataSetIterator([_ds()], batch=16)
    it._telemetry_pending_wait = 0.25       # handed over by hasNext()
    t0 = time.perf_counter()
    etl_fetch(it)
    real = time.perf_counter() - t0
    ev, = [e for e in tracer().events() if e["name"] == "etl"]
    assert ev["dur"] * 1e-6 <= real         # not backdated over the wait
    assert ev["args"]["waited_before_s"] == 0.25
    waited = get_registry().get("dl4j_tpu_step_data_wait_seconds")
    assert waited.count() == 1
    assert 0.25 <= waited.sum() <= 0.25 + real
    assert it._telemetry_pending_wait == 0.0


# ------------------------------------------- the Chrome trace's promises --

def test_chrome_trace_keeps_serving_spans_with_args_and_nesting():
    cb = ContinuousBatcher(_lm(), name="ct", maxSlots=2,
                           pageSize=8).start()
    try:
        ctx = _generate(cb, 5)
    finally:
        cb.shutdown()
    evs = tracer().events()
    prefill, = [e for e in evs if e["name"] == "serving.prefill"]
    assert prefill["args"].pop("bucket") in cb.ladder.seqLens
    assert prefill["args"] == {"replica": "ct", "slot": 0, "depth": 3,
                               "trace_id": ctx.traceId}
    admit = [e for e in evs if e["name"] == "serving.loop.admit"
             and e["ts"] <= prefill["ts"]
             and prefill["ts"] + prefill["dur"] <= e["ts"] + e["dur"]]
    assert len(admit) == 1, "the prefill nests inside ONE admit phase"
    assert admit[0]["args"]["depth"] == 2   # under serving.loop.iteration
    steps = sorted((e for e in evs if e["name"] == "serving.decode.step"),
                   key=lambda e: e["ts"])
    # 4 decode steps, each read one iteration after its dispatch: the
    # first iteration only dispatches, the fifth only reads
    assert len(steps) == 5
    for i, st in enumerate(steps):
        assert st["args"]["replica"] == "ct"
        assert st["args"]["active"] == (1 if i < 4 else 0)
        kids = [e for e in evs if e["tid"] == st["tid"]
                and e["name"].startswith("serving.loop.") and e["ph"] == "X"
                and st["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= st["ts"] + st["dur"]]
        want = [p for p in STEP_PHASES
                if (i < 4 or p not in ("upload", "dispatch"))
                and (i > 0 or p not in ("fetch", "emit", "bookkeep"))]
        assert [k["name"].rsplit(".", 1)[1]
                for k in sorted(kids, key=lambda e: e["ts"])] == want
        assert all(k["args"]["depth"] == st["args"]["depth"] + 1
                   for k in kids)


def test_a_phase_left_by_an_exception_closes_span_and_observation():
    seen = []
    tr = tracer()
    with pytest.raises(ValueError):
        with tr.span("outer"):
            with tr.span("inner", observe=seen.append, why="test") as args:
                args["late"] = 1
                raise ValueError("boom")
    assert tr.open_spans() == []
    inner, outer = tr.events()
    assert (inner["name"], outer["name"]) == ("inner", "outer")
    assert inner["args"] == {"why": "test", "late": 1, "depth": 2}
    assert seen == [pytest.approx(inner["dur"] * 1e-6)]
    with tr.span("next") as _:
        pass
    assert tr.events()[-1]["args"]["depth"] == 1    # depth was restored


def test_every_loop_phase_is_named_and_nothing_switches_spans_on():
    assert SERVING_LOOP_PHASES == ("wait", "admit") + STEP_PHASES
    for gone in ("device_trace_active", "set_device_trace_active"):
        assert not hasattr(telemetry, gone)
        assert not hasattr(telemetry.tracing, gone)
