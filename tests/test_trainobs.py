"""Training-plane observability (ISSUE 20): run-scoped tracing, the
cross-host fleet timeline, and step-time decomposition.

Covers the hybrid-logical-clock merge (causal order across hosts with
skewed wall clocks), run-context propagation into spans and step-phase
exemplars, the ``/v1/runs/<runId>/timeline`` endpoint with its filters,
HealthMonitor run/generation tagging, the elastic-shrink lifecycle event,
and ONE seeded chaos soak asserting a single causally ordered pod
timeline across a leader failover.
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
from deeplearning4j_tpu.fault import (DeviceLossAtStep, ElasticSupervisor,
                                      FaultTolerantTrainer, inject)
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import DeviceMesh, ParallelWrapper
from deeplearning4j_tpu.telemetry import (FleetTimeline, FlightRecorder,
                                          HybridLogicalClock,
                                          MetricsRegistry, RunContext,
                                          TIMELINE_EVENT_KINDS, Tracer,
                                          clear_exemplars, current_run,
                                          exemplar_for, merge_timelines,
                                          observe_step_phase, record_event,
                                          run_scope, set_fleet_timeline,
                                          set_flight_recorder, tracer)
from deeplearning4j_tpu.telemetry.federation import (TelemetryAggregator,
                                                     set_federation_dir)
from deeplearning4j_tpu.telemetry.health import HealthMonitor
from deeplearning4j_tpu.telemetry.http import observability_route

pytestmark = pytest.mark.trainobs


@pytest.fixture(autouse=True)
def fresh_telemetry(tmp_path):
    """Fresh registry/tracer/flight-recorder, no federation config and
    no installed fleet timeline (all are process globals)."""
    prev_reg = telemetry.set_registry(MetricsRegistry())
    prev_tr = telemetry.set_tracer(Tracer())
    prev_fr = telemetry.set_flight_recorder(
        FlightRecorder(capacity=64, dumpDir=str(tmp_path)))
    prev_fed = set_federation_dir(None)
    prev_tl = set_fleet_timeline(None)
    clear_exemplars()
    yield
    clear_exemplars()
    set_fleet_timeline(prev_tl)
    set_federation_dir(prev_fed)
    telemetry.set_flight_recorder(prev_fr)
    telemetry.set_tracer(prev_tr)
    telemetry.set_registry(prev_reg)


def _conf(seed=42):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.01))
            .list()
            .layer(DenseLayer.builder().nIn(4).nOut(8)
                   .activation("relu").build())
            .layer(OutputLayer.builder("mcxent").nOut(3)
                   .activation("softmax").build())
            .setInputType(InputType.feedForward(4)).build())


def _toy(n=64, seed=0, nin=4, nout=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, nin).astype(np.float32)
    w = np.random.RandomState(1).randn(nin, nout)
    y = np.eye(nout, dtype=np.float32)[np.argmax(x @ w, axis=1)]
    return x, y


def _iterator(batch=16):
    x, y = _toy()
    return ListDataSetIterator(
        [DataSet(x[i:i + batch], y[i:i + batch])
         for i in range(0, len(x), batch)], batch=batch)


def _route(path):
    got = observability_route(path)
    assert got is not None, path
    status, body, ctype = got
    assert ctype == "application/json"
    return status, json.loads(body)


# ------------------------------------------------------- vocabulary sync --

def test_lint_vocabulary_matches_runtime():
    """jaxlint cannot import the package (AST-only), so the event-kind
    vocabulary is duplicated in rules_telemetry — the two sets MUST stay
    identical or the linter drifts from what the recorder accepts."""
    from tools.jaxlint import rules_telemetry
    assert rules_telemetry.TIMELINE_EVENT_KINDS == TIMELINE_EVENT_KINDS


# -------------------------------------------------- hybrid logical clock --

class TestHybridLogicalClock:
    def test_tick_strictly_increases_within_one_wall_tick(self):
        clk = HybridLogicalClock()
        stamps = [clk.tick() for _ in range(200)]
        assert stamps == sorted(stamps)
        assert len(set(stamps)) == len(stamps)

    def test_observe_merges_past_remote_stamp(self):
        a, b = HybridLogicalClock(), HybridLogicalClock()
        remote = a.tick()
        # force the remote far into b's future: b must jump past it
        future = (remote[0] + 60_000, remote[1] + 3)
        b.observe(future)
        assert b.tick() > future

    def test_observe_ignores_stale_remote(self):
        clk = HybridLogicalClock()
        now = clk.tick()
        clk.observe((now[0] - 60_000, 99))
        assert clk.tick() > now


# ------------------------------------------------ fleet timeline + merge --

class TestFleetTimeline:
    def test_observe_before_record_orders_across_hosts(self, tmp_path):
        """The causal edge: host B observes host A's stamp before
        recording, so B's event merges strictly after A's no matter
        whose wall clock is ahead."""
        a = FleetTimeline(str(tmp_path), hostId="hostA", runId="r1")
        b = FleetTimeline(str(tmp_path), hostId="hostB", runId="r1")
        e1 = a.record("coord.propose", generation=1)
        b.observe(e1["hlc"])
        b.record("coord.adopt", generation=1)
        merged = merge_timelines(str(tmp_path))
        assert [e["kind"] for e in merged] == ["coord.propose",
                                               "coord.adopt"]
        assert [e["host"] for e in merged] == ["hostA", "hostB"]

    def test_run_agnostic_events_match_any_run_filter(self, tmp_path):
        tl = FleetTimeline(str(tmp_path), hostId="h0")   # no run context
        tl.record("coord.barrier", generation=2)
        with_run = FleetTimeline(str(tmp_path), hostId="h1", runId="rX")
        with_run.record("train.step", step=5)
        got = merge_timelines(str(tmp_path), run_id="rX")
        assert {e["kind"] for e in got} == {"coord.barrier", "train.step"}
        # a different run still sees the run-agnostic coordination event
        got = merge_timelines(str(tmp_path), run_id="rOther")
        assert {e["kind"] for e in got} == {"coord.barrier"}

    def test_filters_and_torn_tail(self, tmp_path):
        tl = FleetTimeline(str(tmp_path), hostId="h0", runId="r1")
        for s in range(6):
            tl.record("train.step", generation=1, step=s)
        tl.record("ckpt.save", generation=1, step=4)
        tl.record("elastic.shrink", generation=2, step=6)
        # torn trailing line (host died mid-append) must be skipped
        fn = next(Path(tmp_path).glob("timeline_*.ndjson"))
        with open(fn, "a", encoding="utf-8") as f:
            f.write('{"kind": "train.st')
        got = merge_timelines(str(tmp_path), kinds=["train.step"],
                              step_min=2, step_max=4)
        assert [e["step"] for e in got] == [2, 3, 4]
        got = merge_timelines(str(tmp_path), generation=2)
        assert [e["kind"] for e in got] == ["elastic.shrink"]

    def test_record_event_is_noop_without_installed_timeline(self):
        assert record_event("train.step", step=1) is None

    def test_recent_window_for_flight_recorder(self, tmp_path):
        tl = FleetTimeline(str(tmp_path), hostId="h0", runId="r1")
        for s in range(100):
            tl.record("train.step", step=s)
        recent = tl.recent(16)
        assert len(recent) == 16
        assert [e["step"] for e in recent] == list(range(84, 100))


# ------------------------------------- run-scoped spans, NDJSON, endpoint --

class TestRunScopedTraining:
    def test_fit_emits_one_run_id_across_spans_timeline_and_endpoint(
            self, tmp_path):
        """The tentpole end-to-end: one fit() mints ONE run id that shows
        up on every step/checkpoint span, in the per-host NDJSON shard,
        and from ``GET /v1/runs/<runId>/timeline``."""
        fed = tmp_path / "fed"
        fed.mkdir()
        set_federation_dir(str(fed))
        net = MultiLayerNetwork(_conf()).init()
        FaultTolerantTrainer(net, str(tmp_path / "ck"), checkpointEveryN=2,
                             keepLast=4).fit(_iterator(), epochs=1)
        assert current_run() is None          # scope ended with fit()

        shards = list(fed.glob("timeline_*.ndjson"))
        assert len(shards) == 1
        events = [json.loads(l) for l in
                  shards[0].read_text().splitlines()]
        kinds = [e["kind"] for e in events]
        assert kinds[0] == "run.start" and kinds[-1] == "run.end"
        assert "train.step" in kinds and "ckpt.save" in kinds
        run_ids = {e["run"] for e in events}
        assert len(run_ids) == 1
        run_id = run_ids.pop()
        assert run_id

        # every step span carries the SAME trace id (the run id)
        spans = [e for e in tracer().events()
                 if e["name"] == "step" and "args" in e]
        assert spans
        assert {s["args"].get("trace_id") for s in spans} == {run_id}
        ckpt = [e for e in tracer().events() if e["name"] == "checkpoint"]
        assert ckpt and all(
            e["args"].get("trace_id") == run_id for e in ckpt)

        # the endpoint serves the merged causal timeline, filterable
        status, doc = _route(f"/v1/runs/{run_id}/timeline")
        assert status == 200
        assert doc["run_id"] == run_id and doc["count"] == len(events)
        assert doc["events"][0]["kind"] == "run.start"
        status, doc = _route(
            f"/v1/runs/{run_id}/timeline?kind=train.step&step_min=2")
        assert status == 200
        assert doc["events"]
        assert all(e["kind"] == "train.step" and e["step"] >= 2
                   for e in doc["events"])
        status, doc = _route("/v1/runs/nosuchrun/timeline")
        assert status == 404 and "unknown run id" in doc["error"]

    def test_endpoint_404s_when_federation_unconfigured(self):
        status, doc = _route("/v1/runs/whatever/timeline")
        assert status == 404
        assert "set_federation_dir" in doc["error"]

    def test_elastic_shrink_lands_on_the_run_timeline(self, tmp_path):
        """Device loss mid-run: the shrink remesh is a lifecycle event on
        the SAME run timeline as the steps around it, tagged with the
        new generation."""
        fed = tmp_path / "fed"
        fed.mkdir()
        set_federation_dir(str(fed))
        conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(0.01))
                .list()
                .layer(DenseLayer.builder().nIn(8).nOut(16)
                       .activation("relu").build())
                .layer(OutputLayer.builder("mcxent").nOut(4)
                       .activation("softmax").build())
                .setInputType(InputType.feedForward(8)).build())
        net = MultiLayerNetwork(conf).init()
        pw = ParallelWrapper(net, mesh=DeviceMesh(
            data=4, devices=jax.devices()[:4]))
        x, y = _toy(n=64, nin=8, nout=4)
        it = ListDataSetIterator(
            [DataSet(x[i:i + 16], y[i:i + 16]) for i in range(0, 64, 16)],
            batch=16)
        es = ElasticSupervisor(pw, str(tmp_path / "el"),
                               checkpointEveryN=2, keepLast=10)
        with inject(DeviceLossAtStep(5, devices=(2, 3))):
            es.fit(it, epochs=2)
        assert [r["direction"] for r in es.stats["remeshes"]] == ["shrink"]

        merged = TelemetryAggregator(str(fed)).timeline()
        shrinks = [e for e in merged if e["kind"] == "elastic.shrink"]
        assert len(shrinks) == 1
        assert shrinks[0]["generation"] >= 1
        run_ids = {e["run"] for e in merged if e["run"] is not None}
        assert len(run_ids) == 1
        assert shrinks[0]["run"] in run_ids


# --------------------------------------------- step-phase decomposition --

class TestStepPhaseExemplars:
    def test_exemplar_resolves_to_generation_and_step(self):
        rc = RunContext.new()
        rc.generation = 3
        with run_scope(rc):
            observe_step_phase("compute", 0.05, step=11)
            observe_step_phase("compute", 0.50, step=12)   # the slow one
            observe_step_phase("compute", 0.10, step=13)
        got = exemplar_for("dl4j_tpu_step_compute_seconds")
        assert got is not None
        assert got["trace_id"] == rc.runId
        assert got["value"] == pytest.approx(0.50)
        assert got["attrs"] == {"generation": 3, "step": 12}

    def test_all_five_phases_register_histograms(self):
        from deeplearning4j_tpu.telemetry.instrument import STEP_PHASES
        for phase in STEP_PHASES:
            observe_step_phase(phase, 0.01, step=1)
            name = f"dl4j_tpu_step_{phase}_seconds"
            h = telemetry.get_registry().get(name)
            assert h is not None, name
            assert h.count() == 1

    def test_unknown_phase_rejected(self):
        with pytest.raises(ValueError):
            observe_step_phase("teleport", 0.01)


# ------------------------------------------------- health-event tagging --

class TestHealthRunTagging:
    class _StubRule:
        name = "stub_rule"

        def __init__(self):
            self.detail = "over threshold"

        def evaluate(self, reg, now):
            return self.detail

    def test_notes_and_transitions_carry_run_and_generation(
            self, tmp_path):
        log = tmp_path / "health.jsonl"
        set_fleet_timeline(FleetTimeline(str(tmp_path), hostId="h0"))
        rule = self._StubRule()
        mon = HealthMonitor(rules=[rule], eventLogPath=str(log))
        rc = RunContext.new()
        rc.generation = 4
        with run_scope(rc):
            mon.note("rollback", step=9)
            mon.evaluate_once(now=0.0)           # firing edge
            rule.detail = None
            mon.evaluate_once(now=1.0)           # resolved edge
        lines = [json.loads(l) for l in log.read_text().splitlines()]
        assert len(lines) == 3
        for rec in lines:
            assert rec["run"] == rc.runId
            assert rec["generation"] == 4
        assert [r["state"] for r in lines] == ["event", "firing",
                                               "resolved"]
        # firing/resolved also land on the fleet timeline
        kinds = [e["kind"] for e in
                 merge_timelines(str(tmp_path), run_id=rc.runId)]
        assert kinds.count("health.firing") == 1
        assert kinds.count("health.resolved") == 1

    def test_untagged_outside_a_run(self, tmp_path):
        log = tmp_path / "health.jsonl"
        mon = HealthMonitor(rules=[], eventLogPath=str(log))
        mon.note("probe", detail="x")
        rec = json.loads(log.read_text().splitlines()[0])
        assert "run" not in rec and "generation" not in rec


# ----------------------------------------------------------- chaos soak --

class TestChaosTimeline:
    def test_leader_failover_yields_one_causal_timeline(self, tmp_path):
        """THE acceptance soak: seed 7 kills the leader mid-barrier; the
        merged pod timeline is ONE causal order (HLC), per-host stamps
        strictly increase, every adopt is preceded by its propose,
        generations are monotonic per host, and the failover itself is
        on the timeline."""
        from deeplearning4j_tpu.fault.chaos import ChaosSoak
        run_dir = str(tmp_path / "run")
        report = ChaosSoak(7, run_dir, events=4).run()
        assert report["ok"], report
        inv = report["invariants"]
        assert inv["timeline_merged_causal"]
        assert inv["timeline_generations_monotonic"]
        assert inv["timeline_covers_events"]
        assert inv["timeline_rollback_windows"]
        assert report["leader_failovers"] == 1

        merged = TelemetryAggregator(run_dir).timeline()
        assert {e["host"] for e in merged} >= {"h0", "h1", "h2"}
        kinds = [e["kind"] for e in merged]
        for kind in ("run.start", "train.step", "ckpt.save",
                     "coord.propose", "coord.adopt", "coord.barrier",
                     "coord.leader_failover", "run.end"):
            assert kind in kinds, kind
        assert set(kinds) <= TIMELINE_EVENT_KINDS
        # merged order IS the causal order
        keys = [tuple(e["hlc"]) + (e["host"],) for e in merged]
        assert keys == sorted(keys)
        # the failover event names the crashed proposer
        fo = next(e for e in merged
                  if e["kind"] == "coord.leader_failover")
        assert fo["failed"] == "h0"
        # the endpoint serves the same story, filtered to coordination
        set_federation_dir(run_dir)
        run_id = next(e["run"] for e in merged if e["run"] is not None)
        status, doc = _route(f"/v1/runs/{run_id}/timeline"
                             "?kind=coord.leader_failover")
        assert status == 200 and doc["count"] == 1
