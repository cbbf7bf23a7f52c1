"""Deterministic chaos-soak harness: seeded fault schedules + invariants.

PR 2/11/12 built the repo's injection-first doctrine one fault at a
time: every recovery path is driven by a deterministic injection, never
discovered in production.  This module composes those injections — and
ISSUE 14's new :class:`~deeplearning4j_tpu.fault.injection.
LeaderCrashMidBarrier` / :class:`~deeplearning4j_tpu.fault.injection.
KillAtBarrier` — into a SEEDED soak: one short coordinated training run
peppered with device loss, host partitions, slow leases, corrupt
checkpoints, torn telemetry snapshots, stalls, preemptions and
coordinator deaths at the protocol's worst moments, followed by the
standing invariants every PR has promised individually:

1. **exactly one sealed checkpoint lineage** — every verified manifest
   belongs to one monotonic generation sequence; no stale writer sealed
   over the survivors' history;
2. **trajectory matches the uninterrupted reference** — the final
   params/loss equal a fault-free run of the same model and stream
   (the GSPMD step's math is mesh-size invariant, so shrink/grow must
   be placement, never math);
3. **exactly-once data delivery** — every batch advanced the optimizer
   exactly once per epoch (counters line up; the trajectory check
   witnesses the content);
4. **flat steady-state jit-miss counter** — all the re-meshing left no
   retrace landmine behind.

The schedule is a pure function of ``seed`` (:func:`build_schedule`) —
``tools/chaos.py --seed N`` replays the identical event list
bit-for-bit, which is what makes a chaos FAILURE a bug report instead
of an anecdote.

The pod around the trainer is simulated in-process: the training host
``h1`` runs a real :class:`~deeplearning4j_tpu.fault.elastic.
ElasticSupervisor` over a real :class:`~deeplearning4j_tpu.fault.
coordination.PodCoordinator`, while phantom peers ``h0`` (the LEADER —
deliberately lower than the trainer, so leader death exercises the
failover path in the trainer) and ``h2`` are driven by background
poller threads that crash, partition and heal on schedule.

Usage::

    from deeplearning4j_tpu.fault.chaos import ChaosSoak
    report = ChaosSoak(seed=7, runDir=tmp).run()
    assert report["ok"], report

or, from a shell, ``python tools/chaos.py --seed 7``.
"""
from __future__ import annotations

import logging
import math
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.fault import injection as _inj
from deeplearning4j_tpu.telemetry import (coord_metrics, flight_recorder,
                                          get_registry)

__all__ = ["ChaosSoak", "build_schedule", "EVENT_KINDS",
           "ServingChaosSoak", "build_serving_schedule",
           "SERVING_EVENT_KINDS"]

log = logging.getLogger(__name__)

#: the leader phantom (lowest host id: its death mid-barrier lands on
#: the TRAINER as a failover) and the follower phantom
LEADER_PEER = "h0"
TRAINER_HOST = "h1"
FOLLOWER_PEER = "h2"

#: primary event kinds the scheduler draws from (paired companions —
#: capacity_return, heal_peer, heal_heartbeat — ride along and do not
#: count toward the requested event budget)
EVENT_KINDS = (
    "device_loss", "partition_peer", "delayed_heartbeat",
    "corrupt_checkpoint", "torn_snapshot", "stall", "leader_crash",
    "kill_at_barrier", "preempt",
)

#: per-schedule caps: the soak is a protocol workout, not a demolition —
#: e.g. at most 2 of the 4 mesh devices may die so a valid mesh always
#: survives, and exactly one leader crash keeps the failover counter
#: assertable (== number of crashes fired)
_CAPS = {"device_loss": 2, "partition_peer": 1, "delayed_heartbeat": 1,
         "corrupt_checkpoint": 1, "torn_snapshot": 1, "stall": 2,
         "leader_crash": 1, "kill_at_barrier": 1, "preempt": 1}


def build_schedule(seed: int, totalSteps: int, events: int = 4,
                   meshDevices=(0, 1, 2, 3),
                   cadence: int = 2) -> List[dict]:
    """The seeded event schedule: a PURE function of its arguments
    (``np.random.RandomState`` — stable across runs and platforms), so
    the same seed replays the same faults at the same steps bit-for-bit.

    Constraints keep every draw survivable and assertable: at most two
    mesh devices die (a valid mesh always remains, and the lowest mesh
    device never dies so a data axis survives), host-level faults that
    would mask each other are exclusive (``leader_crash`` owns ``h0``;
    partitions and slow leases target ``h2``), and destructive draws
    are paired with their recovery (device loss -> capacity return,
    partition -> heal) a few steps later — a recovery scheduled past
    the end of the run simply never fires, which is itself a scenario
    (the run ends shrunken; the trajectory must STILL match)."""
    # jaxlint: sync-ok -- seed is a Python int CLI/test argument, not a device scalar
    rng = np.random.RandomState(int(seed))
    counts: Dict[str, int] = {k: 0 for k in EVENT_KINDS}
    out: List[dict] = []
    # jaxlint: sync-ok -- mesh device ids here are Python ints from the schedule config
    lossPool = sorted(int(d) for d in meshDevices)[1:]
    # jaxlint: sync-ok -- events is a Python int CLI/test argument
    events = max(0, int(events))
    guard = 0
    while sum(counts.values()) < events and guard < 200:
        guard += 1
        kind = EVENT_KINDS[int(rng.randint(len(EVENT_KINDS)))]
        if counts[kind] >= _CAPS[kind]:
            continue
        step = int(rng.randint(1, max(2, totalSteps - 1)))
        if kind == "device_loss":
            if not lossPool:
                continue
            dev = lossPool.pop(int(rng.randint(len(lossPool))))
            out.append({"step": step, "kind": kind, "devices": [dev]})
            out.append({"step": step + 2 + int(rng.randint(0, 6)),
                        "kind": "capacity_return", "devices": [dev]})
        elif kind == "partition_peer":
            out.append({"step": step, "kind": kind,
                        "host": FOLLOWER_PEER})
            out.append({"step": step + 2 + int(rng.randint(0, 4)),
                        "kind": "heal_peer", "host": FOLLOWER_PEER})
        elif kind == "delayed_heartbeat":
            out.append({"step": step, "kind": kind,
                        "host": FOLLOWER_PEER,
                        "seconds": round(float(rng.uniform(1.5, 3.0)),
                                         3)})
            out.append({"step": step + 2 + int(rng.randint(0, 4)),
                        "kind": "heal_heartbeat",
                        "host": FOLLOWER_PEER})
        elif kind == "corrupt_checkpoint":
            boundaries = list(range(cadence, max(cadence, totalSteps)
                                    + 1, cadence))
            out.append({"step": boundaries[int(
                rng.randint(len(boundaries)))], "kind": kind})
        elif kind == "torn_snapshot":
            out.append({"step": step, "kind": kind})
        elif kind == "stall":
            out.append({"step": step, "kind": kind, "seconds": 0.05})
        elif kind == "leader_crash":
            out.append({"step": step, "kind": kind,
                        "host": LEADER_PEER})
        elif kind == "kill_at_barrier":
            out.append({"step": step, "kind": kind,
                        "host": FOLLOWER_PEER})
        elif kind == "preempt":
            out.append({"step": step, "kind": kind})
        counts[kind] += 1
    drawn = sum(counts.values())
    if drawn < events:
        # no silent caps: the report's whole value is being a faithful
        # artifact — an operator asking for a denser workout than the
        # per-kind caps allow must see the shortfall, not assume it ran
        log.warning("chaos schedule capped at %d primary events "
                    "(%d requested): per-kind caps %s exhausted",
                    drawn, events, dict(_CAPS))
    out.sort(key=lambda e: (int(e["step"]), str(e["kind"])))
    return out


class _PreemptOnce(_inj.PreemptAtStep):
    """One-shot preemption: the library fault re-raises on every pass
    through its step, which is right for a process that really dies
    (the injector dies with it) — the in-process soak resumes with the
    SAME injector, so the replay after restore must sail past the step
    it already died at."""

    def __init__(self, step: int):
        super().__init__(step)
        self.fired = False

    def before_step(self, step, net, ds):
        if not self.fired and step >= self.step:
            self.fired = True
            raise _inj.SimulatedPreemption(
                f"preempted before step {step} (chaos)")


class _CorruptSealedAt(_inj.Fault):
    """Corrupt the checkpoint for ``step`` AFTER its (async) seal
    lands.  The library's :class:`CorruptCheckpointAtStep` fires the
    moment the save is issued, which under PR 11's ``asyncSeal``
    default races the orbax write still in flight — there is nothing
    on disk to corrupt yet.  What the soak wants to exercise is the
    restore-time checksum fallback, so join the sealer first, then
    flip bytes under the sealed manifest's nose."""

    def __init__(self, step: int, ckpt):
        self.step = int(step)
        self.ckpt = ckpt
        self.fired = False

    def after_checkpoint(self, step, step_path):
        if self.fired or step != self.step:
            return
        self.fired = True
        self.ckpt.waitUntilFinished()
        _inj.corrupt_checkpoint(self.ckpt.directory, step)


class _ActAt(_inj.Fault):
    """One-shot harness action fired at the first step >= ``step`` —
    the glue that turns a schedule entry into registry arms, lease
    narrowing, healing, or torn-snapshot writes."""

    def __init__(self, step: int, action):
        self.step = int(step)
        self.action = action
        self.fired = False

    def before_step(self, step, net, ds):
        if not self.fired and step >= self.step:
            self.fired = True
            self.action()


class _TrackedFault(_inj.Fault):
    """Wrap a library fault so its FIRST firing lands in the report and
    in ``dl4j_tpu_coord_chaos_events_total{event=...}`` — the soak's
    own observability (a schedule entry that never fired is a finding
    too)."""

    def __init__(self, kind: str, inner: _inj.Fault, firedLog: List[str]):
        self.kind = str(kind)
        self.inner = inner
        self.firedLog = firedLog
        self.fired = False

    def _mark(self) -> None:
        if not self.fired:
            self.fired = True
            self.firedLog.append(self.kind)
            coord_metrics().chaos_events().inc(event=self.kind)

    def _state(self):
        return (getattr(self.inner, "fired", None),
                getattr(self.inner, "times", None))

    def before_step(self, step, net, ds):
        pre = self._state()
        try:
            out = self.inner.before_step(step, net, ds)
        except BaseException:
            self._mark()
            raise
        if self._state() != pre:
            self._mark()
        return out

    def after_checkpoint(self, step, step_path):
        pre = self._state()
        self.inner.after_checkpoint(step, step_path)
        if self._state() != pre:
            self._mark()


class _CrashUnderLoad(_inj.Fault):
    """The serving soak's replica crash, armed with a sequence to hand
    over.  A crash that finds its replica idle is consumed by the
    health probe and nothing fails over — what a wall-clock schedule
    gets whenever the router sent that replica nothing.  So the crash
    is armed and ``launch()`` puts one streaming client on the replica
    under the replica set's lock: retirement takes the same lock, so
    the replica is still routed when the sequence lands on it, and an
    armed or consumed crash stops its loop before it finishes anything.
    Whether the loop or the probe consumes the crash, the sequence is
    there to be handed over."""

    def __init__(self, inner: "_inj.ReplicaCrashAtStep", lock, launch):
        self.inner = inner
        self.lock = lock
        self.launch = launch

    @property
    def fired(self) -> bool:
        return self.inner.fired

    def before_step(self, step, net, ds):
        if self.inner.fired:
            return
        with self.lock:
            self.inner.before_step(step, net, ds)
            if self.inner.fired:
                self.launch(self.inner.replica)


class _PhantomPeer:
    """An in-process stand-in for another pod host: a real
    :class:`PodCoordinator` whose ``poll()`` loop runs on a background
    thread, so it proposes, acks barriers, gets evicted, crashes and
    re-admits exactly like a remote process would — without spawning
    one (the soak's determinism and runtime budget both want a single
    interpreter)."""

    def __init__(self, runDir: str, hostId: str, devices, **kw):
        from deeplearning4j_tpu.fault.coordination import PodCoordinator
        self.hostId = str(hostId)
        self.coord = PodCoordinator(runDir, hostId, devices=devices,
                                    **kw)
        self.crashed = False
        self.errors: List[str] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "_PhantomPeer":
        self.coord.start()
        self._thread = threading.Thread(
            target=self._loop, name=f"chaos-peer-{self.hostId}",
            daemon=True)
        self._thread.start()
        return self

    def _loop(self) -> None:
        from deeplearning4j_tpu.fault.coordination import (
            CoordinationError, PodEvictedError)
        while not self._stop.wait(0.05):
            try:
                self.coord.poll()
            except _inj.SimulatedPreemption:
                # the injected coordinator death: stop the lease THREAD
                # too, not just rely on the partition registry — a dead
                # process writes nothing, and a later heal_host on this
                # host (or inject() exit clearing the registry) must not
                # resurrect a heartbeat whose poller is gone, or every
                # peer's barrier waits forever on a live-looking corpse
                self.crashed = True
                self.coord.lease.stop()
                return
            except PodEvictedError:
                # keep heartbeating and polling: re-admission is the
                # only way back in, and it needs fresh beats
                continue
            except CoordinationError as e:
                self.errors.append(f"{type(e).__name__}: {e}")
            except Exception as e:      # a phantom bug must surface in
                self.errors.append(f"{type(e).__name__}: {e}")  # report
                return

    def narrow(self) -> None:
        """Drop this peer's highest published device — the minimal
        topology change that forces the leader's next proposal (the
        trigger half of the barrier-death events)."""
        devs = list(self.coord.lease.devices)
        if devs:
            self.coord.setHealthyDevices(devs[:-1])

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        self.coord.stop()


class ChaosSoak:
    """One seeded chaos-soak run: schedule -> coordinated training loop
    -> invariants.  See the module docstring for the contract; every
    knob that shapes the schedule is part of the determinism key."""

    def __init__(self, seed: int, runDir: str, *, epochs: int = 2,
                 batchesPerEpoch: int = 4, batchSize: int = 16,
                 events: int = 4, checkpointEveryN: int = 2,
                 leaseTimeout: float = 1.0,
                 heartbeatInterval: float = 0.1,
                 barrierTimeout: float = 60.0):
        self.seed = int(seed)
        self.runDir = str(runDir)
        self.epochs = int(epochs)
        self.batchesPerEpoch = int(batchesPerEpoch)
        self.batchSize = int(batchSize)
        self.events = int(events)
        self.checkpointEveryN = int(checkpointEveryN)
        self.leaseTimeout = float(leaseTimeout)
        self.heartbeatInterval = float(heartbeatInterval)
        self.barrierTimeout = float(barrierTimeout)
        self.totalSteps = self.epochs * self.batchesPerEpoch

    # -- schedule --------------------------------------------------------
    def schedule(self) -> List[dict]:
        return build_schedule(self.seed, self.totalSteps,
                              events=self.events,
                              cadence=self.checkpointEveryN)

    # -- model/data (deterministic, shared with the reference run) ------
    def _mlp(self):
        from deeplearning4j_tpu.learning import Adam
        from deeplearning4j_tpu.models import MultiLayerNetwork
        from deeplearning4j_tpu.nn.conf import (InputType,
                                                NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Adam(0.01)).list()
                .layer(DenseLayer.builder().nIn(8).nOut(16)
                       .activation("relu").build())
                .layer(OutputLayer.builder("mcxent").nOut(4)
                       .activation("softmax").build())
                .setInputType(InputType.feedForward(8)).build())
        net = MultiLayerNetwork(conf)
        net.init()
        return net

    def _data(self):
        n = self.batchesPerEpoch * self.batchSize
        rng = np.random.RandomState(0)
        x = rng.randn(n, 8).astype(np.float32)
        w = np.random.RandomState(1).randn(8, 4)
        y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, axis=1)]
        return x, y

    def _batches(self, x, y):
        from deeplearning4j_tpu.datasets import (DataSet,
                                                 ListDataSetIterator)
        per = self.batchSize
        return ListDataSetIterator(
            [DataSet(x[i * per:(i + 1) * per], y[i * per:(i + 1) * per])
             for i in range(self.batchesPerEpoch)], batch=per)

    # -- faults ----------------------------------------------------------
    def _buildFaults(self, schedule: List[dict],
                     peers: Dict[str, "_PhantomPeer"], ckpt,
                     firedLog: List[str]) -> List[_inj.Fault]:
        faults: List[_inj.Fault] = []

        def act(entry, action):
            inner = _ActAt(entry["step"], action)
            faults.append(_TrackedFault(entry["kind"], inner, firedLog))

        for e in schedule:
            kind = e["kind"]
            if kind == "device_loss":
                faults.append(_TrackedFault(kind, _inj.DeviceLossAtStep(
                    e["step"], devices=tuple(e["devices"])), firedLog))
            elif kind == "capacity_return":
                faults.append(_TrackedFault(
                    kind, _inj.RestoreCapacityAtStep(
                        e["step"], devices=tuple(e["devices"])),
                    firedLog))
            elif kind == "partition_peer":
                faults.append(_TrackedFault(kind, _inj.PartitionedHost(
                    e["host"], step=e["step"]), firedLog))
            elif kind == "heal_peer":
                act(e, lambda h=e["host"]: _inj.heal_host(h))
            elif kind == "delayed_heartbeat":
                faults.append(_TrackedFault(kind, _inj.DelayedHeartbeat(
                    e["host"], seconds=e["seconds"],
                    fromStep=e["step"]), firedLog))
            elif kind == "heal_heartbeat":
                act(e, lambda h=e["host"]:
                    _inj.set_heartbeat_delay(h, 0.0))
            elif kind == "corrupt_checkpoint":
                faults.append(_TrackedFault(
                    kind, _CorruptSealedAt(e["step"], ckpt), firedLog))
            elif kind == "torn_snapshot":
                act(e, self._writeTornSnapshot)
            elif kind == "stall":
                faults.append(_TrackedFault(kind, _inj.StallAtStep(
                    e["step"], seconds=e["seconds"]), firedLog))
            elif kind == "leader_crash":
                peer = peers[e["host"]]

                def crash(p=peer, h=e["host"]):
                    # arm BEFORE the trigger: the narrowed lease makes
                    # the leader propose, the armed registry kills it
                    # between its publish and its own barrier ack
                    _inj.arm_leader_crash(h)
                    p.narrow()
                act(e, crash)
            elif kind == "kill_at_barrier":
                peer = peers[e["host"]]

                def kill(p=peer, h=e["host"]):
                    _inj.arm_barrier_kill(h)
                    p.narrow()
                act(e, kill)
            elif kind == "preempt":
                faults.append(_TrackedFault(kind, _PreemptOnce(
                    e["step"]), firedLog))
            else:
                raise ValueError(f"unknown chaos event kind {kind!r}")
        return faults

    def _writeTornSnapshot(self) -> None:
        """Half a federation snapshot, as a dying worker would leave it
        — the aggregator must skip and count it, never crash or merge
        garbage."""
        path = os.path.join(self.runDir, "metrics_chaos-torn.json")
        with open(path, "w", encoding="utf-8") as f:
            f.write('{"host": "chaos-torn", "metrics": {"dl4j_')

    # -- the run ---------------------------------------------------------
    def run(self) -> dict:
        import jax

        from deeplearning4j_tpu.fault.coordination import PodCoordinator
        from deeplearning4j_tpu.fault.elastic import ElasticSupervisor
        from deeplearning4j_tpu.parallel import (DeviceMesh,
                                                 ParallelWrapper)
        from deeplearning4j_tpu.telemetry.federation import \
            TelemetryAggregator

        schedule = self.schedule()
        firedLog: List[str] = []
        os.makedirs(self.runDir, exist_ok=True)
        x, y = self._data()

        # the uninterrupted reference: same model seed, same stream, no
        # faults, bare single-device net — the GSPMD step's mesh-size
        # invariance (asserted since PR 10) makes it the oracle for the
        # whole soak regardless of where the mesh lands
        ref = self._mlp()
        for _ in range(self.epochs):
            it = self._batches(x, y)
            while it.hasNext():
                ref.fit(it.next())
        # jaxlint: sync-ok -- reference-run readback for the post-soak invariant, not the step path
        refParams = np.asarray(ref.params().numpy()).astype(np.float64)
        # jaxlint: sync-ok -- reference loss readback for the post-soak invariant, not the step path
        refLoss = float(ref.score())

        devs = jax.devices()[:4]
        hosts = [LEADER_PEER, TRAINER_HOST, FOLLOWER_PEER]
        kw = dict(leaseTimeout=self.leaseTimeout,
                  heartbeatInterval=self.heartbeatInterval,
                  barrierTimeout=self.barrierTimeout)
        leader = _PhantomPeer(self.runDir, LEADER_PEER, [8, 9], **kw)
        follower = _PhantomPeer(self.runDir, FOLLOWER_PEER, [10, 11],
                                **kw)
        coord = PodCoordinator(self.runDir, TRAINER_HOST,
                               # jaxlint: sync-ok -- device .id is a Python int from the backend client, not a device scalar
                               devices=[int(d.id) for d in devs], **kw)
        peers = {LEADER_PEER: leader, FOLLOWER_PEER: follower}

        reg = get_registry()

        def counter(name, **labels):
            m = reg.get(name)
            if m is None:
                return 0.0
            try:
                return float(m.value(**labels))
            except (ValueError, AttributeError):
                return 0.0

        failovers0 = counter("dl4j_tpu_coord_leader_failovers_total")
        report = {"seed": self.seed, "steps": self.totalSteps,
                  "epochs": self.epochs,
                  "batchesPerEpoch": self.batchesPerEpoch,
                  "events": sum(1 for e in schedule
                                if e["kind"] in EVENT_KINDS),
                  "schedule": schedule}
        net = self._mlp()
        pw = ParallelWrapper(net, mesh=DeviceMesh(data=4, devices=devs))
        sup = None
        # post-fit drain: a late proposal (a heal/readmission landing
        # near the end of the stream) leaves a phantom blocked in its
        # barrier waiting for the trainer's ack — with fit() over,
        # nobody would ever write it and the phantom would time out as
        # a false positive.  The drain thread keeps acking on the
        # trainer's behalf until shutdown.
        drainStop = threading.Event()

        def drain():
            from deeplearning4j_tpu.fault.coordination import \
                CoordinationError
            while not drainStop.wait(0.05):
                try:
                    coord.poll()
                except CoordinationError:
                    continue
                except Exception:
                    continue

        drainThread = threading.Thread(target=drain, daemon=True,
                                       name="chaos-drain")
        t0 = time.perf_counter()
        try:
            leader.coord.lease.write_now()
            follower.coord.lease.write_now()
            coord.start()
            leader.coord.establish(hosts, timeout=30)
            coord.establish(hosts, timeout=30)
            follower.coord.establish(hosts, timeout=30)
            leader.start()
            follower.start()
            sup = ElasticSupervisor(
                pw, os.path.join(self.runDir, "ckpt"),
                checkpointEveryN=self.checkpointEveryN, keepLast=10,
                coordinator=coord)
            faults = self._buildFaults(schedule, peers, sup.ckpt,
                                       firedLog)
            with _inj.inject(*faults):
                while True:
                    try:
                        sup.fit(self._batches(x, y),
                                epochs=self.epochs)
                        break
                    except _inj.SimulatedPreemption:
                        # the preempt event: same entrypoint, rerun —
                        # auto-resume from the last sealed step is the
                        # PR 2 contract under test here
                        continue
            drainThread.start()
            self._settle(coord)
            report["leader_failovers"] = counter(
                "dl4j_tpu_coord_leader_failovers_total") - failovers0
            report["invariants"] = self._checkInvariants(
                sup, net, pw, coord, refParams, refLoss, x, y,
                TelemetryAggregator, counter, schedule,
                failovers=report["leader_failovers"])
            report["generation"] = coord.generation
            report["peer_errors"] = leader.errors + follower.errors
            report["fired"] = list(firedLog)
            report["ok"] = bool(all(report["invariants"].values())
                                and not report["peer_errors"])
        except (KeyboardInterrupt, SystemExit):
            # a cancelled soak is a cancellation, not a chaos finding —
            # cleanup still runs (finally), the interrupt propagates
            raise
        except BaseException as e:
            report["invariants"] = {}
            report["error"] = f"{type(e).__name__}: {e}"
            report["fired"] = list(firedLog)
            report["ok"] = False
        finally:
            report["seconds"] = round(time.perf_counter() - t0, 3)
            leader.stop()
            follower.stop()
            drainStop.set()
            if drainThread.is_alive():
                drainThread.join(timeout=10.0)
            coord.stop()
            if sup is not None:
                try:
                    sup.close()
                except Exception:
                    pass
        return report

    def _settle(self, coord) -> None:
        """Let the coordination protocol quiesce before reading final
        state: an event scheduled near the end of the stream (a leader
        crashing after the trainer's last boundary) leaves its orphaned
        plan to the post-fit drain — reading the failover counter or
        the generation before the drain adopts it would report a
        protocol IN FLIGHT as a protocol that never happened."""
        deadline = time.monotonic() + max(10.0, 3 * self.leaseTimeout)
        linger = max(self.heartbeatInterval, 0.2)
        while time.monotonic() < deadline:
            plan = coord.currentPlan() or {}
            if int(plan.get("generation", 0)) > coord.generation:
                time.sleep(0.1)     # the drain is mid-adoption
                continue
            # adopted everything published; a just-crashed leader's
            # in-flight publish lands within a heartbeat — linger one
            time.sleep(linger)
            plan = coord.currentPlan() or {}
            if int(plan.get("generation", 0)) <= coord.generation:
                return

    def _checkInvariants(self, sup, net, pw, coord, refParams, refLoss,
                         x, y, TelemetryAggregator, counter,
                         schedule, failovers: int = 0) -> Dict[str, bool]:
        from deeplearning4j_tpu.datasets import DataSet
        inv: Dict[str, bool] = {}
        # 1. exactly one sealed checkpoint lineage
        ckpt = sup.ckpt
        ckpt.waitUntilFinished()
        # jaxlint: sync-ok -- orbax step numbers are Python ints, not device scalars
        steps = sorted(int(s) for s in ckpt.allSteps())
        sealed = [s for s in steps if ckpt.verifyStep(s)]
        gens = []
        for s in sealed:
            g = ckpt.readMetadata(s).get("generation")
            if g is not None:
                gens.append(g)      # manifest JSON: already an int
        inv["single_sealed_lineage"] = bool(
            sealed and ckpt.latestValidStep() is not None
            and all(a <= b for a, b in zip(gens, gens[1:]))
            and (not gens or max(gens) <= coord.generation))
        # 2. trajectory matches the uninterrupted reference
        # jaxlint: sync-ok -- post-soak invariant readback, not the step path
        params = np.asarray(net.params().numpy()).astype(np.float64)
        lossOk = sup.lastLoss is not None and \
            abs(sup.lastLoss - refLoss) <= 1e-5
        inv["trajectory_matches_reference"] = bool(
            params.shape == refParams.shape
            and np.allclose(params, refParams, rtol=2e-4, atol=2e-5)
            and lossOk)
        # 3. exactly-once data delivery: every batch advanced the
        # optimizer exactly once per epoch, across every rollback,
        # re-mesh replay and resume (the trajectory check above
        # witnesses the CONTENT; this witnesses the count)
        inv["exactly_once_delivery"] = bool(
            net.iterationCount == self.totalSteps
            and net.epochCount == self.epochs)
        # 4. flat steady-state jit-miss counter on the final mesh
        miss0 = counter("dl4j_tpu_mesh_jit_cache_misses_total")
        for _ in range(3):
            pw.fitDataSet(DataSet(x[:self.batchSize],
                                  y[:self.batchSize]))
        inv["flat_jit_misses"] = counter(
            "dl4j_tpu_mesh_jit_cache_misses_total") == miss0
        # 5. ONE causally ordered pod timeline: every host's NDJSON file
        # merges in HLC order — per-host stamps strictly increase, every
        # adopt sorts after the propose that caused it (the cross-host
        # edge the leader's plan stamp creates), and the trainer plus at
        # least one phantom peer contributed (a single-host "merge"
        # would prove nothing)
        timeline = TelemetryAggregator(self.runDir).timeline()
        keys = [tuple(e.get("hlc") or (0, 0)) + (e.get("host"),)
                for e in timeline]
        perHost: Dict[str, list] = {}
        for e in timeline:
            perHost.setdefault(str(e.get("host")), []).append(
                tuple(e.get("hlc") or (0, 0)))
        proposeAt: Dict[int, int] = {}
        causal = True
        for i, e in enumerate(timeline):
            gen = e.get("generation")
            if e.get("kind") == "coord.propose":
                proposeAt.setdefault(gen, i)
            elif e.get("kind") == "coord.adopt":
                if gen not in proposeAt or proposeAt[gen] >= i:
                    causal = False
        inv["timeline_merged_causal"] = bool(
            timeline and keys == sorted(keys) and causal
            and len(perHost) >= 2
            and all(all(a < b for a, b in zip(v, v[1:]))
                    for v in perHost.values()))
        # 6. generations are monotonic per host along the merged order
        genSeq: Dict[str, list] = {}
        for e in timeline:
            if e.get("kind") == "coord.adopt":
                genSeq.setdefault(str(e.get("host")), []).append(
                    int(e.get("generation", 0)))
        inv["timeline_generations_monotonic"] = all(
            all(a <= b for a, b in zip(v, v[1:]))
            for v in genSeq.values())
        # 7. the timeline COVERS what actually happened: a counted
        # leader failover and any shrink re-mesh must appear as events
        kinds = {e.get("kind") for e in timeline}
        expected = set()
        if failovers > 0:
            expected.add("coord.leader_failover")
        if any(r.get("direction") == "shrink"
               for r in sup.stats.get("remeshes", ())):
            expected.add("elastic.shrink")
        inv["timeline_covers_events"] = expected <= kinds
        # 8. every rollback's surrounding timeline window landed in the
        # FlightRecorder ring (vacuously true when the seed produced
        # no divergence)
        rollbacks = [e for e in timeline
                     if e.get("kind") == "ckpt.rollback"]
        windows = [r for r in flight_recorder().snapshot()
                   if r.get("event") == "timeline_window"]

        def _covered(rb):
            return any(any(ev.get("hlc") == rb.get("hlc")
                           and ev.get("host") == rb.get("host")
                           for ev in w.get("events", ()))
                       for w in windows)

        inv["timeline_rollback_windows"] = all(
            _covered(rb) for rb in rollbacks)
        # event-conditional checks
        if any(e["kind"] == "torn_snapshot" for e in schedule):
            agg = TelemetryAggregator(self.runDir,
                                      localRegistry=get_registry())
            try:
                agg.merged()
                inv["torn_snapshot_skipped"] = any(
                    "chaos-torn" in f for f in agg.skippedFiles)
            except Exception:
                inv["torn_snapshot_skipped"] = False
        return inv


# ===================================================================
# Serving-tier chaos soak (ISSUE 17)
# ===================================================================

#: serving event kinds the serving scheduler draws from
SERVING_EVENT_KINDS = ("replica_crash", "slow_replica", "client_hangup",
                       "deadline_storm")

#: per-schedule caps — one crash and one brownout keep the retirement
#: count assertable; two hangups and one storm exercise cancellation
#: without starving the exactly-once clients of decode slots
_SERVING_CAPS = {"replica_crash": 1, "slow_replica": 1,
                 "client_hangup": 2, "deadline_storm": 1}

#: the replica index the crash always targets / the brownout always
#: targets — fixed (not drawn) so replica 0 always survives to adopt
#: failovers and the invariants stay assertable for every seed
_CRASH_REPLICA_IDX = 1
_SLOW_REPLICA_IDX = 2


def build_serving_schedule(seed: int, totalTicks: int,
                           events: int = 4) -> List[dict]:
    """The seeded serving-fault schedule: a PURE function of its
    arguments (``np.random.RandomState``), same replayability contract
    as :func:`build_schedule`.  Every draw lands in the FIRST HALF of
    the soak's tick budget so its recovery (probe retirement, failover
    replay, drain) completes inside the run."""
    # jaxlint: sync-ok -- seed/ticks/events are Python ints, not device scalars
    rng = np.random.RandomState(int(seed))
    counts: Dict[str, int] = {k: 0 for k in SERVING_EVENT_KINDS}
    out: List[dict] = []
    events = max(0, int(events))  # jaxlint: sync-ok -- Python int argument
    totalTicks = max(2, int(totalTicks))  # jaxlint: sync-ok -- Python int argument
    guard = 0
    while sum(counts.values()) < events and guard < 200:
        guard += 1
        kind = SERVING_EVENT_KINDS[int(
            rng.randint(len(SERVING_EVENT_KINDS)))]
        if counts[kind] >= _SERVING_CAPS[kind]:
            continue
        tick = int(rng.randint(1, max(2, totalTicks // 2)))
        if kind == "replica_crash":
            out.append({"step": tick, "kind": kind,
                        "replica": _CRASH_REPLICA_IDX})
        elif kind == "slow_replica":
            out.append({"step": tick, "kind": kind,
                        "replica": _SLOW_REPLICA_IDX,
                        "seconds": round(float(rng.uniform(0.05, 0.15)),
                                         3),
                        "untilStep": tick + 6 + int(rng.randint(0, 6))})
        elif kind == "client_hangup":
            out.append({"step": tick, "kind": kind,
                        "token": int(rng.randint(1, 4))})
        elif kind == "deadline_storm":
            out.append({"step": tick, "kind": kind,
                        "requests": int(rng.randint(2, 5))})
        counts[kind] += 1
    drawn = sum(counts.values())
    if drawn < events:
        log.warning("serving chaos schedule capped at %d primary events "
                    "(%d requested): per-kind caps %s exhausted",
                    drawn, events, dict(_SERVING_CAPS))
    out.sort(key=lambda e: (int(e["step"]), str(e["kind"])))
    return out


class ServingChaosSoak:
    """One seeded serving chaos soak: ragged streaming clients against a
    3-replica :class:`~deeplearning4j_tpu.remote.scheduler.ReplicaSet`
    while the schedule crashes one replica, browns out another, hangs
    up clients mid-stream and fires a burst of already-expired
    requests.  Invariants:

    1. **exactly-once tokens** — every surviving client's stream equals
       the uninterrupted single-model reference bit-for-bit: zero
       dropped and zero duplicated tokens across the failover replay;
    2. **all KV pages freed** — every surviving replica's pool drains
       back to fully free (crashed streams, hangups and sheds
       included);
    3. **flat steady-state jit-miss counter** on every survivor —
       failover replay and probe traffic compiled nothing new;
    4. **p99 bounded** while the replica died (generous cap — this
       asserts no wedge, not a latency SLO);
    5. **deadline storm shed 504** — every expired request raised
       ``DeadlineExceeded`` at admission and none ever held a slot.

    The scheduler module is imported lazily: ``fault/__init__`` imports
    this module at package import, and ``remote.scheduler`` imports
    ``fault.injection`` — a top-level import here would cycle."""

    def __init__(self, seed: int, *, replicas: int = 3, clients: int = 6,
                 events: int = 4, totalTicks: int = 40,
                 maxNewTokens: int = 8, vocab: int = 48, maxLen: int = 64,
                 tickSeconds: float = 0.02, maxSeconds: float = 120.0):
        self.seed = int(seed)
        self.replicas = max(2, int(replicas))
        self.clients = int(clients)
        self.events = int(events)
        self.totalTicks = int(totalTicks)
        self.maxNewTokens = int(maxNewTokens)
        self.vocab = int(vocab)
        self.maxLen = int(maxLen)
        self.tickSeconds = float(tickSeconds)
        self.maxSeconds = float(maxSeconds)
        self.name = f"soak{self.seed}"

    def schedule(self) -> List[dict]:
        return build_serving_schedule(self.seed, self.totalTicks,
                                      events=self.events)

    # -- model -----------------------------------------------------------
    def _lm(self):
        from deeplearning4j_tpu.nlp.transformer import TransformerLM
        # every replica gets its OWN instance with IDENTICAL weights
        # (same seed): greedy decode then replays bit-identically on a
        # survivor, and each instance owns its own jit cache — required
        # for the flat-jit-miss invariant, since a crashed replica's
        # _invalidateFns pops caches on ITS model only
        return TransformerLM(vocabSize=self.vocab, nLayers=1, nHeads=2,
                             headSize=8, maxLen=self.maxLen, seed=11)

    def _factory(self, idx: int):
        from deeplearning4j_tpu.remote.scheduler import ContinuousBatcher
        return ContinuousBatcher(self._lm(), maxSlots=2, pageSize=8)

    def _prompts(self) -> List[np.ndarray]:
        rng = np.random.RandomState(self.seed + 1)
        out = []
        for _ in range(self.clients):
            n = int(rng.randint(3, 11))
            out.append(rng.randint(0, self.vocab,
                                   size=(n,)).astype(np.int32))
        return out

    # -- scheduled actions ----------------------------------------------
    def _launchHangup(self, rs, prompts, rng, threads, k: int) -> None:
        """A doomed streaming client: reads ``k`` tokens, hangs up.  Its
        sequence must cancel at the next step boundary and free its
        pages — the page invariant is the witness."""
        prompt = prompts[int(rng.randint(len(prompts)))]

        def run():
            try:
                gen = rs.submitStream({
                    "tokens": prompt.tolist(),
                    "maxNewTokens": self.maxNewTokens,
                    "keepAliveSeconds": 0.05})
                got = 0
                try:
                    for tok in gen:
                        if not isinstance(tok, int):
                            continue            # keep-alive sentinel
                        got += 1
                        if got >= k:
                            break
                finally:
                    gen.close()
            except Exception:
                pass        # a doomed client's errors are expected noise
        th = threading.Thread(target=run, daemon=True,
                              name="soak-hangup-client")
        th.start()
        threads.append(th)

    def _launchOnReplica(self, rs, replica: str, prompts, threads,
                         handedOver, errors) -> None:
        """A streaming client enqueued on the replica named ``replica``
        (the call ``rs.submitStream`` makes once it has picked one),
        read to its end on a thread of its own into ``handedOver``
        (prompt 0's tokens).  Called with ``rs._lock`` held."""
        ex = next((e for e in rs._replicas if e.name == replica), None)
        if ex is None:
            return                      # retired before its crash tick
        gen = ex.submitStream({"tokens": prompts[0].tolist(),
                               "maxNewTokens": self.maxNewTokens,
                               "keepAliveSeconds": 0.1})

        def run():
            try:
                handedOver.append([t for t in gen if isinstance(t, int)])
            except Exception as e:
                errors.append(f"client on {replica}: "
                              f"{type(e).__name__}: {e}")
        th = threading.Thread(target=run, daemon=True,
                              name="soak-crash-client")
        th.start()
        threads.append(th)

    def _fireStorm(self, rs, prompts, rng, results, n: int) -> None:
        """``n`` already-expired requests: each must shed 504
        (``DeadlineExceeded``) at admission, never holding a slot."""
        from deeplearning4j_tpu.remote.serving import DeadlineExceeded
        prompt = prompts[int(rng.randint(len(prompts)))]
        for _ in range(n):
            try:
                rs.submit({"tokens": prompt.tolist(),
                           "maxNewTokens": self.maxNewTokens,
                           "deadlineSeconds": 0.0})
                results.append(False)       # served an expired request
            except DeadlineExceeded:
                results.append(True)
            except Exception:
                results.append(False)

    def _buildFaults(self, rs, prompts, rng, hangupThreads, stormResults,
                     firedLog: List[str], handedOver,
                     errors) -> List[_inj.Fault]:
        faults: List[_inj.Fault] = []
        for e in self.schedule():
            kind = e["kind"]
            if kind == "replica_crash":
                faults.append(_TrackedFault(kind, _CrashUnderLoad(
                    _inj.ReplicaCrashAtStep(
                        f"{self.name}/{e['replica']}", step=e["step"]),
                    rs._lock,
                    lambda replica: self._launchOnReplica(
                        rs, replica, prompts, hangupThreads, handedOver,
                        errors)), firedLog))
            elif kind == "slow_replica":
                faults.append(_TrackedFault(kind, _inj.SlowReplica(
                    f"{self.name}/{e['replica']}", seconds=e["seconds"],
                    step=e["step"], untilStep=e["untilStep"]), firedLog))
            elif kind == "client_hangup":
                faults.append(_TrackedFault(kind, _inj.ClientHangupAtToken(
                    e["step"], token=e["token"],
                    action=lambda k: self._launchHangup(
                        rs, prompts, rng, hangupThreads, k)), firedLog))
            elif kind == "deadline_storm":
                faults.append(_TrackedFault(kind, _inj.DeadlineStorm(
                    e["step"], requests=e["requests"],
                    action=lambda n: self._fireStorm(
                        rs, prompts, rng, stormResults, n)), firedLog))
            else:
                raise ValueError(f"unknown serving event kind {kind!r}")
        return faults

    # -- metric helpers --------------------------------------------------
    @staticmethod
    def _sumCells(name: str, **match) -> float:
        """Sum a labeled metric's cells matching ``match`` — the soak
        reads per-replica models (``soakN/0`` ...) without enumerating
        them."""
        m = get_registry().get(name)
        if m is None:
            return 0.0
        d = m.data()
        names = d["labelnames"]
        total = 0.0
        for labelvalues, value in d["cells"]:
            cell = dict(zip(names, labelvalues))
            if all(cell.get(k) == v for k, v in match.items()):
                total += float(value)  # jaxlint: sync-ok -- registry cell values are host floats
        return total

    @staticmethod
    def _latencyQuantile(name: str, q: float,
                         modelPrefix: str) -> Optional[float]:
        """Quantile over a latency histogram's buckets MERGED across
        every cell whose model label starts with ``modelPrefix`` — the
        soak's replicas observe under per-replica names (``soakN/0``
        ...), and the report wants the fleet-wide TTFT/ITL, not one
        replica's.  Upper-bound attribution, same convention as
        ``serving.histogram_quantile``."""
        m = get_registry().get(name)
        if m is None:
            return None
        d = m.data()
        names = d["labelnames"]
        # jaxlint: sync-ok -- registry bucket bounds are host floats
        buckets = [float(b) for b in d.get("buckets", ())]
        agg = [0] * (len(buckets) + 1)
        for labelvalues, cell in d["cells"]:
            labels = dict(zip(names, labelvalues))
            if not str(labels.get("model", "")).startswith(modelPrefix):
                continue
            for i, c in enumerate(cell.get("counts", [])[:len(agg)]):
                agg[i] += int(c)  # jaxlint: sync-ok -- registry bucket counts are host ints
        total = sum(agg)
        if total <= 0:
            return None
        rank = q * total
        cum, prev = 0, 0.0
        for bound, c in zip(buckets + [float("inf")], agg):
            cum += c
            if cum >= rank:
                return bound if not math.isinf(bound) else prev
            prev = bound
        return prev

    # -- the run ---------------------------------------------------------
    def run(self) -> dict:
        from deeplearning4j_tpu.remote.scheduler import ReplicaSet

        schedule = self.schedule()
        firedLog: List[str] = []
        prompts = self._prompts()
        rng = np.random.RandomState(self.seed + 2)

        # the uninterrupted reference: ONE fault-free model decodes every
        # prompt — greedy decode is deterministic, so this is the oracle
        # every surviving stream must match bit-for-bit
        refLm = self._lm()
        # jaxlint: sync-ok -- reference-run readback for the invariant oracle, not the serving path
        refs = [[int(t) for t in
                 refLm.generate(p[None, :], self.maxNewTokens)[0]]
                for p in prompts]

        rs = ReplicaSet(self._factory, name=self.name,
                        replicas=self.replicas,
                        maxReplicas=self.replicas + 1,
                        drainTimeout=5.0, probeInterval=0.05,
                        probeTimeout=2.0, probeFailThreshold=2,
                        seed=self.seed)
        report = {"seed": self.seed, "ticks": self.totalTicks,
                  "clients": self.clients, "replicas": self.replicas,
                  "events": len(schedule), "schedule": schedule}
        results: List[Optional[List[int]]] = [None] * self.clients
        errors: List[str] = []
        latencies: List[float] = []
        hangupThreads: List[threading.Thread] = []
        stormResults: List[bool] = []
        handedOver: List[List[int]] = []    # streams a crash was armed under
        clientThreads: List[threading.Thread] = []
        t0 = time.perf_counter()
        try:
            rs.start()
            miss0 = self._sumCells(
                "dl4j_tpu_serving_compile_cache_misses_total")
            failovers0 = self._sumCells(
                "dl4j_tpu_serving_failovers_total", model=self.name)
            sheds0 = self._sumCells(
                "dl4j_tpu_serving_deadline_sheds_total",
                stage="admission")

            def client(i: int, delay: float):
                time.sleep(delay)
                c0 = time.perf_counter()
                try:
                    gen = rs.submitStream({
                        "tokens": prompts[i].tolist(),
                        "maxNewTokens": self.maxNewTokens,
                        "keepAliveSeconds": 0.1})
                    got = [t for t in gen if isinstance(t, int)]
                    results[i] = got
                    latencies.append(time.perf_counter() - c0)
                except Exception as e:
                    errors.append(f"client {i}: {type(e).__name__}: {e}")

            # ragged arrivals: clients land spread over the first half
            # of the tick budget, overlapping the scheduled faults
            for i in range(self.clients):
                delay = float(rng.uniform(
                    0, self.totalTicks * self.tickSeconds * 0.5))
                th = threading.Thread(target=client, args=(i, delay),
                                      daemon=True,
                                      name=f"soak-client-{i}")
                th.start()
                clientThreads.append(th)

            faults = self._buildFaults(rs, prompts, rng, hangupThreads,
                                       stormResults, firedLog, handedOver,
                                       errors)
            hardStop = time.monotonic() + self.maxSeconds
            with _inj.inject(*faults) as inj:
                tick = 0
                while (tick < self.totalTicks or
                       any(th.is_alive() for th in clientThreads)):
                    if time.monotonic() > hardStop:
                        errors.append("soak exceeded maxSeconds")
                        break
                    tick += 1
                    inj.before_step(tick, None, None)
                    time.sleep(self.tickSeconds)
                for th in clientThreads + hangupThreads:
                    th.join(timeout=30.0)
                # settle: hangup cancellations retire at the next step
                # boundary; wait for every survivor to go idle so the
                # page invariant reads quiesced state
                settleEnd = time.monotonic() + 10.0
                while time.monotonic() < settleEnd:
                    with rs._lock:
                        live = list(rs._replicas)
                    if all(not ex.busy() and ex.queuedRows() == 0
                           for ex in live):
                        break
                    time.sleep(0.05)

            inv: Dict[str, bool] = {}
            crashFired = "replica_crash" in firedLog
            inv["exactly_once_tokens"] = bool(
                not errors and
                all(results[i] == refs[i] for i in range(self.clients))
                and all(got == refs[0] for got in handedOver))
            with rs._lock:
                live = list(rs._replicas)
            inv["all_pages_freed"] = bool(live) and all(
                ex.pool.freePages() == ex.pool.numPages - 1
                for ex in live)
            inv["flat_jit_misses"] = self._sumCells(
                "dl4j_tpu_serving_compile_cache_misses_total") == miss0
            # jaxlint: sync-ok -- latencies are host-side wall-clock floats
            p99 = float(np.percentile(latencies, 99)) \
                if latencies else float("inf")
            inv["p99_bounded"] = p99 <= self.maxSeconds / 2
            if crashFired:
                inv["crashed_replica_retired"] = \
                    rs.replicaCount() == self.replicas - 1
            if "deadline_storm" in firedLog:
                inv["deadline_shed_504"] = bool(
                    stormResults and all(stormResults) and
                    self._sumCells(
                        "dl4j_tpu_serving_deadline_sheds_total",
                        stage="admission") - sheds0
                    >= len(stormResults))
            report["invariants"] = inv
            report["fired"] = list(firedLog)
            report["errors"] = list(errors)
            report["p99_seconds"] = round(p99, 4) if latencies else None
            report["failovers"] = self._sumCells(
                "dl4j_tpu_serving_failovers_total",
                model=self.name) - failovers0
            # latency decomposition across the soak's replicas: the
            # fleet-wide TTFT and inter-token gaps the chaos actually
            # cost (the ITL p99 CONTAINS any failover gap by design)
            for metric, key in (
                    ("dl4j_tpu_serving_ttft_seconds", "ttft"),
                    ("dl4j_tpu_serving_inter_token_seconds", "itl")):
                for q, tag in ((0.5, "p50"), (0.99, "p99")):
                    v = self._latencyQuantile(metric, q, self.name)
                    report[f"{key}_{tag}_seconds"] = \
                        round(v, 6) if v is not None else None
            report["ok"] = bool(all(inv.values()) and not errors)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            report["invariants"] = {}
            report["error"] = f"{type(e).__name__}: {e}"
            report["fired"] = list(firedLog)
            report["ok"] = False
        finally:
            report["seconds"] = round(time.perf_counter() - t0, 3)
            rs.shutdown()
        return report
