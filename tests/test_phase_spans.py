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

The second clock (ISSUE 50): a loop phase also reads its thread's CPU clock
(``cpu_s`` in the Chrome event, ``..._loop_phase_offcpu_seconds`` = wall
less CPU), a streamed token's hand-off is booked by its consumer
(``..._stream_token_seconds_total{stage}``, the Chrome event
``serving.stream.queued``), and every Python thread's CPU time is added up
by role whenever the registry is read
(``dl4j_tpu_process_thread_cpu_seconds_total{role}``).
"""
import gc
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from test_cbatch import _by_hand, _run_out
from tools import emit_cost, span_cost

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
OFFCPU_HIST = "dl4j_tpu_serving_loop_phase_offcpu_seconds"
IDLE_HIST = "dl4j_tpu_serving_device_idle_seconds"
STREAM_SECONDS = "dl4j_tpu_serving_stream_token_seconds_total"
STREAM_TOKENS = "dl4j_tpu_serving_stream_tokens_delivered_total"
THREAD_CPU = "dl4j_tpu_process_thread_cpu_seconds_total"


@pytest.fixture(autouse=True)
def fresh_telemetry(monkeypatch):
    prev_reg = telemetry.set_registry(MetricsRegistry())
    prev_tr = set_tracer(Tracer())
    # a CPU clock that costs nothing to read is read in every iteration
    # (a loaded machine must not decide what these tests count)
    monkeypatch.setattr(scheduler, "_cpu_clock_read_seconds", lambda: 0.0)
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
                                   "gc_seconds", "offcpu_seconds",
                                   "threads", "queued"}
        assert st["args"]["seconds"] == pytest.approx(e["dur"] * 1e-6,
                                                      abs=1e-5)
        assert st["args"]["threads"] >= 2 and st["args"]["queued"] == 0
        assert 0.0 <= st["args"]["gc_seconds"] <= st["args"]["seconds"]
        # a stretch is no one phase's: the phases inside it say theirs
        assert st["args"]["offcpu_seconds"] is None
    # a loop phase of 0.1 s or more leaves one too, with what of it the
    # thread did not run for; a wait slice does not
    seen = len(_stalls())
    cb._observePhase("emit", 0.2, 0.05)
    cb._observePhase("wait", 0.2, 0.0)
    emit, = _stalls()[seen:]
    assert emit["args"]["phase"] == "emit"
    assert emit["args"]["offcpu_seconds"] == pytest.approx(0.15)


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


# ------------------------------------- the loop thread's second clock --

@pytest.fixture(scope="module")
def two_clocks():
    """One stream of 12 tokens through a real loop, a registry and a
    tracer of its own: both phase histograms' cells and the events."""
    prev_reg = telemetry.set_registry(MetricsRegistry())
    prev_tr = set_tracer(Tracer())
    cb = ContinuousBatcher(_lm(), name="tc", maxSlots=2, pageSize=8)
    cb._clock.every = 1             # whatever a read costs here
    cb.start()
    try:
        _generate(cb, 12)
        time.sleep(0.12)            # and a slice of waiting
    finally:
        cb.shutdown()
        seen = {"wall": _cells(LOOP_HIST, "tc", "phase"),
                "off": _cells(OFFCPU_HIST, "tc", "phase"),
                "events": tracer().events()}
        set_tracer(prev_tr)
        telemetry.set_registry(prev_reg)
    return seen


@pytest.mark.parametrize("phase", SERVING_LOOP_PHASES)
def test_both_phase_histograms_count_alike_and_offcpu_is_inside_wall(
        two_clocks, phase):
    (n_wall, wall), (n_off, off) = (two_clocks[k][phase]
                                    for k in ("wall", "off"))
    assert n_off == n_wall >= 1
    assert 0.0 <= off <= wall
    # the Chrome events of the phase carry the thread's CPU seconds, and
    # wall less CPU over them is what the second histogram holds
    evs = [e for e in two_clocks["events"]
           if e["name"] == "serving.loop." + phase]
    assert len(evs) == n_wall
    assert all(0.0 <= e["args"]["cpu_s"] for e in evs)
    assert sum(max(0.0, e["dur"] * 1e-6 - e["args"]["cpu_s"])
               for e in evs) == pytest.approx(off, rel=1e-6, abs=1e-9)
    if phase == "wait":
        # the thread sleeps in it by design: off the CPU all but all of it
        assert off >= 0.8 * wall


def test_only_a_loop_phase_reads_the_cpu_clock(two_clocks):
    by_name = {}
    for e in two_clocks["events"]:
        by_name.setdefault(e["name"], []).append("cpu_s" in e["args"])
    phases = {"serving.loop." + p for p in SERVING_LOOP_PHASES}
    assert phases <= set(by_name)
    for name, has in by_name.items():
        assert all(has) if name in phases else not any(has), name
    assert {"serving.loop.iteration", "serving.decode.step",
            "serving.prefill", "serving.state.write"} <= set(by_name)
    # nor a span entered from any other thread, with an observer or not
    seen = []
    with tracer().span("elsewhere", observe=seen.append):
        pass
    ev, = [e for e in tracer().events() if e["name"] == "elsewhere"]
    assert "cpu_s" not in ev["args"]
    assert seen == [pytest.approx(ev["dur"] * 1e-6)]


def _spin(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def _ran_late():
    """Seconds this thread has been runnable and waited for a core, by
    the kernel's books; 0.0 where it keeps none."""
    try:
        with open("/proc/self/task/%d/schedstat"
                  % threading.get_native_id()) as f:
            return int(f.read().split()[1]) * 1e-9
    except (OSError, ValueError, IndexError):
        return 0.0


@pytest.mark.parametrize("body, seconds, low, high", [
    (time.sleep, 0.05, 0.8, 1.0),   # a phase that sleeps: off the CPU
    (_spin, 0.01, 0.0, 0.2),        # a phase that spins: on it
], ids=["sleep", "spin"])
def test_offcpu_tells_a_phase_that_waits_from_one_that_runs(
        body, seconds, low, high):
    cb = span_cost.loop_thread()    # a phase needs no model
    cb.name = "oc"
    shares = []
    for _ in range(10):             # a busy machine may take a try's core:
        late = _ran_late()          # what it waited for one is the machine's
        with cb._phase("emit"):
            body(seconds)
        late = _ran_late() - late
        (n, wall), (m, off) = (_cells(h, "oc", "phase")["emit"]
                               for h in (LOOP_HIST, OFFCPU_HIST))
        assert n == m == len(shares) + 1 and wall >= seconds * n
        assert off <= wall
        ev = [e for e in tracer().events()
              if e["name"] == "serving.loop.emit"][-1]
        dur = ev["dur"] * 1e-6
        shares.append(max(0.0, dur - ev["args"]["cpu_s"] - late) / dur)
        if low <= shares[-1] <= high:
            break
    assert low <= shares[-1] <= high, shares


def test_a_dear_cpu_clock_is_read_in_one_iteration_of_some(monkeypatch):
    # 3.2 us a read (a sandbox between the thread and its kernel): the
    # clock is read in every fourth iteration, whole iterations of it
    monkeypatch.setattr(scheduler, "_cpu_clock_read_seconds",
                        lambda: 3.2e-6)
    cb = ContinuousBatcher(_lm(), name="dear", maxSlots=2,
                           pageSize=8).start()
    try:
        assert cb._clock.every == 4
        _generate(cb, 30)
    finally:
        cb.shutdown()
    wall, off = (_cells(h, "dear", "phase") for h in (LOOP_HIST,
                                                      OFFCPU_HIST))
    its = wall["admit"][0]
    assert off["admit"][0] == its // 4
    for p in STEP_PHASES:
        assert its // 4 - 1 <= off[p][0] <= its // 4 < wall[p][0]
    # a step that was read was read whole: a mean a step is a mean
    assert off["fetch"][0] == off["emit"][0] == off["bookkeep"][0]
    read = [e for e in tracer().events()
            if e["name"].startswith("serving.loop.")
            and "cpu_s" in e.get("args", {})]
    assert len(read) == sum(n for p, (n, _s) in off.items())


def test_a_coarse_cpu_clock_still_adds_up_phase_by_phase():
    clock = scheduler._LoopClock()
    # a clock that moves 10 ms at a time under phases of 1 and 3 ms: emit
    # is on the CPU all the time, bookkeep a third of it
    ticks = {"emit": [0.0] * 9 + [0.01], "bookkeep": [0.0, 0.01] + [0.0] * 8}
    off = {"emit": [], "bookkeep": []}
    for _ in range(5):
        for i in range(10):
            off["emit"].append(clock.offcpu("emit", 0.001, ticks["emit"][i]))
            off["bookkeep"].append(
                clock.offcpu("bookkeep", 0.003, ticks["bookkeep"][i]))
    assert all(0.0 <= v <= 0.001 for v in off["emit"])
    assert all(0.0 <= v <= 0.003 for v in off["bookkeep"])
    # wall less off-CPU is the CPU time read, up to the tick in hand
    assert 0.05 - sum(off["emit"]) == pytest.approx(0.05 - 0.009, abs=1e-9)
    assert 0.15 - sum(off["bookkeep"]) == pytest.approx(0.05, abs=1e-9)
    # and a clock that counts nanoseconds keeps no credit: wall less CPU
    assert clock.offcpu("grow", 0.002, 0.0005) == pytest.approx(0.0015)
    assert clock.offcpu("grow", 0.002, 0.002) == 0.0
    assert clock.offcpu("grow", 0.002, 0.0) == 0.002


# -------------------------------------------------- the token's hand-off --

def _stream_counts(model):
    reg = get_registry()
    seconds = reg.get(STREAM_SECONDS)
    return {"tokens": reg.get(STREAM_TOKENS).value(model=model),
            "queued": seconds.value(model=model, stage="queued"),
            "write": seconds.value(model=model, stage="write")}


@pytest.mark.parametrize("how", ["finished", "cancelled", "replayed"])
def test_tokens_delivered_are_the_tokens_a_consumer_took(how):
    quota = 40                      # over a flush of 32 and a tail
    ref = _lm().generate(np.asarray([[1, 2, 3]], np.int32), quota)[0]
    cb = _by_hand(_lm(), "hd-" + how, maxSlots=2)
    try:
        gen = cb.submitStream({"tokens": [1, 2, 3], "maxNewTokens": quota})
        seq = cb._queue[-1]
        take = 35 if how == "cancelled" else quota
        if how == "replayed":
            for _ in range(9):
                cb._iterate()
            assert 0 < seq.streamed < quota
            cb._preempt(cb._slotSeq.index(seq))
        if how == "cancelled":
            while seq.streamed <= take:
                cb._iterate()
            got = [next(gen) for _ in range(take)]
            gen.close()             # the client hung up: the loop learns
            assert seq.cancelled    # it at its next step
            _run_out(cb)
            assert seq.streamed > take      # put for nobody: not delivered
        else:
            _run_out(cb)
            got = list(gen)         # the end's sentinel is no token
            assert seq.streamed == quota and seq.streamSkip == 0
            assert seq.restarts == (how == "replayed")
        assert got == ref[:take].tolist()
        counts = _stream_counts(cb.name)
        assert counts["tokens"] == take
        assert counts["queued"] > 0.0 and counts["write"] > 0.0
    finally:
        cb.shutdown()


def test_a_slow_consumers_time_is_write_and_the_next_tokens_lie_in_queued():
    quota, nap = 4, 0.05
    cb = _by_hand(_lm(), "slow", maxSlots=2)
    try:
        gen = cb.submitStream({"tokens": [1, 2, 3], "maxNewTokens": quota,
                               "keepAliveSeconds": 0.01})
        # a keep-alive while nothing has been computed counts nothing
        assert not isinstance(next(gen), int)
        _run_out(cb)                # all four lie in the queue from here
        t0 = time.perf_counter()
        got = []
        for tok in gen:
            got.append(tok)
            time.sleep(nap)         # a client that reads slowly
        elapsed = time.perf_counter() - t0
        assert len(got) == quota
        counts = _stream_counts("slow")
        assert counts["tokens"] == quota
        # every token's consumer slept before it asked for the next
        assert quota * nap <= counts["write"] <= elapsed
        # and token i lay there through the i naps before it
        lay = nap * sum(range(quota))
        assert lay <= counts["queued"] <= lay + elapsed
        evs = _spans("serving.stream.queued")
        # the others lay 10 ms or more; the first only for the steps
        # that followed its own
        assert quota - 1 <= len(evs) <= quota
        assert all(e["args"] == {"replica": "slow", "row": 0} for e in evs)
        assert sum(e["dur"] for e in evs) * 1e-6 <= counts["queued"]
        # on the consumer's track, which is this thread's and no loop's
        with tracer().span("here"):
            pass
        assert {e["tid"] for e in evs} == {_spans("here")[0]["tid"]}
    finally:
        cb.shutdown()


# ------------------------------------------------------- CPU, by role --

def _role_seconds():
    """{role: seconds} off a whole read of the registry (the read is what
    adds the threads' gains up)."""
    data = get_registry().snapshot().get(THREAD_CPU)
    if data is None:
        return {}
    return {key[0]: v for key, v in data["cells"]}


def _burn(cpu_seconds, then=lambda: None):
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_seconds:
        pass
    then()


@pytest.mark.parametrize("name, role", [
    ("cbatch-lm", "serving_loop"),
    ("serving-handler-7", "serving_handler"),
    ("telemetry-snapshot-h0", "telemetry"),
    ("metrics-retention", "telemetry"),
    ("replica-probe-r0", "telemetry"),
    ("Thread-9 (worker)", "python_other"),
    ("wedge-reap-cbatch-lm", "python_other"),
])
def test_a_threads_cpu_is_booked_under_the_role_its_name_says(name, role):
    assert telemetry.thread_role(name) == role
    before = _role_seconds()
    th = threading.Thread(target=_burn, args=(0.2,), name=name)
    th.start()
    th.join(30)
    assert not th.is_alive()
    # it has ended, but not yet been looked for: found gone, its gain is
    # lost, and nothing falls
    lost = _role_seconds()
    assert all(lost[r] >= before.get(r, 0.0) for r in lost)
    live = threading.Event()
    th = threading.Thread(target=_burn, args=(0.2, lambda: live.wait(30)),
                          name=name)
    th.start()
    try:
        deadline = time.monotonic() + 30
        while _role_seconds().get(role, 0.0) - lost.get(role, 0.0) < 0.15:
            assert time.monotonic() < deadline, "its 0.2 s never showed"
            time.sleep(0.02)
    finally:
        live.set()
        th.join(30)
    after = _role_seconds()
    # its 0.2 s and, under python_other, this thread's polling
    assert 0.15 <= after[role] - lost.get(role, 0.0) <= 0.3 + (
        0.3 if role == "python_other" else 0.0)
    # monotone: what a dead thread had booked stays
    ended = _role_seconds()
    assert all(ended[r] >= after[r] for r in after)


@pytest.mark.parametrize("prefix, role, name, want", [
    ("etl-worker-", "etl", "etl-worker-3", "etl"),      # an owner's own
    ("cbatch-", "elsewhere", "cbatch-lm", "serving_loop"),  # first said wins
    ("telemetry-", "telemetry", "telemetry-x", "telemetry"),    # said twice
], ids=["new", "taken", "again"])
def test_whoever_makes_a_thread_registers_its_role(prefix, role, name, want,
                                                   monkeypatch):
    roles = telemetry.registry._thread_roles
    monkeypatch.setattr(telemetry.registry, "_thread_roles", list(roles))
    telemetry.register_thread_role(prefix, role)
    telemetry.register_thread_role(prefix, role)
    said = telemetry.registry._thread_roles
    assert said.count((prefix, role)) == 1
    assert len(said) == len(roles) + ((prefix, role) not in roles)
    assert telemetry.thread_role(name) == want


@pytest.fixture
def burnt():
    """A live thread named for the loop's role that has burnt 0.2 s of
    CPU and now waits, and the role's seconds before it was started."""
    before = _role_seconds().get("serving_loop", 0.0)
    done, live = threading.Event(), threading.Event()
    th = threading.Thread(
        target=_burn, args=(0.2, lambda: (done.set(), live.wait(30))),
        name="cbatch-burnt")
    th.start()
    try:
        assert done.wait(30)
        yield th, before
    finally:
        live.set()
        th.join(30)


def test_two_readers_at_once_book_a_threads_cpu_once(burnt, monkeypatch):
    th, before = burnt
    read = telemetry.registry._cpu_clock_ns
    second = []

    def slow(thread):
        # the first reader, at that thread's clock, lets a second reader
        # start and run into it
        if thread is th and not second:
            second.append(threading.Thread(target=get_registry().snapshot))
            second[0].start()
            time.sleep(0.05)
        return read(thread)

    monkeypatch.setattr(telemetry.registry, "_cpu_clock_ns", slow)
    get_registry().snapshot()
    second[0].join(30)
    gained = get_registry().get(THREAD_CPU).value(role="serving_loop") \
        - before
    assert 0.19 <= gained <= 0.3


def test_a_reading_older_than_the_last_books_nothing(burnt, monkeypatch):
    th, before = burnt
    read = telemetry.registry._cpu_clock_ns
    _role_seconds()                 # seen once, at its 0.2 s
    at = read(th)
    readings = iter([at + 120_000_000, at + 100_000_000, at + 130_000_000])
    monkeypatch.setattr(telemetry.registry, "_cpu_clock_ns",
                        lambda t: next(readings) if t is th else read(t))
    gained = []
    for _ in range(3):
        gained.append(_role_seconds()["serving_loop"] - before)
    base = gained[0] - 0.12
    assert 0.19 <= base <= 0.3
    assert [g - base for g in gained] == pytest.approx([0.12, 0.12, 0.13])


def test_a_thread_without_a_cpu_clock_is_passed_over(burnt, monkeypatch):
    th, before = burnt
    read = telemetry.registry._cpu_clock_ns
    monkeypatch.setattr(telemetry.registry, "_cpu_clock_ns",
                        lambda t: None if t is th else read(t))
    assert _role_seconds().get("serving_loop", 0.0) == before
    # a platform with no such clock at all: the call raises, nothing read
    monkeypatch.undo()
    monkeypatch.delattr(time, "clock_gettime_ns")
    assert read(th) is None


def test_the_http_fronts_request_threads_say_their_role():
    from deeplearning4j_tpu.remote.server import RequestThreadsHTTPServer
    from http.server import BaseHTTPRequestHandler
    import urllib.request
    names = []

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            names.append(threading.current_thread().name)
            self.send_response(204)
            self.end_headers()

    httpd = RequestThreadsHTTPServer(("127.0.0.1", 0), Handler)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    try:
        url = "http://127.0.0.1:%d/" % httpd.server_address[1]
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 204
    finally:
        httpd.shutdown()
        httpd.server_close()
        th.join(10)
    assert len(names) == 1
    assert telemetry.thread_role(names[0]) == "serving_handler"


def test_a_registry_that_is_not_the_processs_books_no_thread():
    mine = get_registry()
    other = MetricsRegistry()
    prev = telemetry.set_registry(other)    # the hook is other's too now
    telemetry.set_registry(prev)
    assert prev is mine
    other.snapshot()
    assert other.get(THREAD_CPU) is None
    # a hook runs at the top of both whole reads, once each
    calls = []
    other.add_collect_hook(calls.append)
    other.add_collect_hook(calls.append)
    other.snapshot()
    other.exposition()
    assert calls == [other, other]


# -------------------------------------- what one step's delivery costs --

def test_the_per_sink_cost_harness_runs():
    out = emit_cost.measure(slots=4, steps=6, rounds=1)
    assert out["consumers_ended"]
    assert set(out["sink_us_a_token"]) == set(emit_cost.SINKS)
    for variant in ("all",) + emit_cost.SINKS:
        # counts only: a time means something on the chip's host alone
        assert out[variant]["wall_us_a_token"] > 0.0
        assert 0.0 <= out[variant]["cpu_us_a_token"]
    # the stubs took their sinks out and put them back
    from deeplearning4j_tpu.telemetry import (observe_exemplar,
                                              timeline_store)
    assert scheduler.observe_exemplar is observe_exemplar
    assert scheduler.timeline_store is timeline_store
    assert get_registry().get(
        "dl4j_tpu_serving_decode_tokens_total").value(model="m") == \
        4 * 7 * 4                   # every variant but its own stub


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
