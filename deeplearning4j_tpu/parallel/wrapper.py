"""ParallelWrapper — single-node multi-device data-parallel training.

Reference: deeplearning4j-scaleout-parallelwrapper
``org/deeplearning4j/parallelism/ParallelWrapper.java`` — the reference
clones the model per device, runs a trainer thread per device, and
averages params / shares threshold-encoded gradients every N iterations
(SURVEY.md §2.6 P1).

TPU-native design: no clones, no trainer threads, no averaging step.  The
wrapper is now a thin FACADE over
:class:`~deeplearning4j_tpu.parallel.meshtrainer.MeshTrainer`: one
``ShardingPlan`` over the mesh axes places params/optimizer state and the
batch, and ONE jitted donated train step (compiled with the plan's in/out
shardings) executes every mesh shape — pure DP, DP x TP, DP + ZeRO-1,
expert-parallel MoE, sequence (ring attention) and pipeline (GPipe)
meshes all through ``MeshTrainer.step``.  GSPMD inserts the gradient
all-reduce (psum over ICI) inside the executable; this is mathematically
the reference's synchronous averaging with averagingFrequency=1 at ICI
speed.  The ``trainingMode``/``averagingFrequency``/threshold knobs are
accepted for API parity and ignored (documented no-ops, SURVEY.md §7.1).
"""
from __future__ import annotations

import time
from typing import Optional

import jax

from deeplearning4j_tpu.parallel.mesh import DeviceMesh
from deeplearning4j_tpu.telemetry import (ReplicaTimingListener,
                                          get_registry, tracer)


class TrainingMode:
    AVERAGING = "AVERAGING"
    SHARED_GRADIENTS = "SHARED_GRADIENTS"
    CUSTOM = "CUSTOM"


class ParallelWrapper:
    """``ParallelWrapper.Builder(net).workers(N)...build()`` parity."""

    def __init__(self, model, mesh: Optional[DeviceMesh] = None,
                 tensorParallel: bool = False, **_ignored):
        self.model = model
        self.mesh = mesh or DeviceMesh()
        self.tensorParallel = tensorParallel
        self._trainer = None

    # -- builder ---------------------------------------------------------
    class Builder:
        def __init__(self, model):
            self._model = model
            self._kw = {}

        def workers(self, n: int):
            self._kw["workers"] = n
            return self

        def trainingMode(self, mode: str):
            self._kw["trainingMode"] = mode  # accepted, no-op (see module doc)
            return self

        def averagingFrequency(self, n: int):
            self._kw["averagingFrequency"] = n  # no-op
            return self

        def prefetchBuffer(self, n: int):
            self._kw["prefetchBuffer"] = n  # no-op (input pipeline is async)
            return self

        def thresholdAlgorithm(self, algo):
            self._kw["thresholdAlgorithm"] = algo  # no-op: ICI needs no compression
            return self

        def residualPostProcessor(self, p):
            self._kw["residualPostProcessor"] = p  # no-op
            return self

        def workspaceMode(self, m):
            return self

        def build(self) -> "ParallelWrapper":
            workers = self._kw.get("workers")
            mesh = None
            if workers:
                mesh = DeviceMesh(data=workers,
                                  devices=jax.devices()[:workers])
            return ParallelWrapper(self._model, mesh=mesh)

    # -- the one stepping path -------------------------------------------
    def trainer(self):
        """The MeshTrainer this facade steps through (built lazily; rebuilt
        when the model object or its ZeRO tag changed — e.g.
        ``zero.ZeroStage1`` applied between fits)."""
        from deeplearning4j_tpu.parallel.meshtrainer import MeshTrainer
        tr = self._trainer
        zero_now = getattr(self.model, "_zero1Axis", None) is not None
        if tr is None or tr.net is not self.model or \
                tr.plan.zero1 != zero_now:
            tr = MeshTrainer(self.model, mesh=self.mesh,
                             tensorParallel=self.tensorParallel)
            self._trainer = tr
        return tr

    def remesh(self, mesh: DeviceMesh, reshard: bool = True) -> None:
        """Swap this wrapper onto a different mesh (elastic shrink/grow,
        straggler eviction).  Rebuilds the ShardingPlan with the same
        TP/ZeRO flags, reshards live state through the trainer's
        plan-to-plan path (``reshard=True``; a shrink that is about to
        restore a sealed checkpoint passes ``False``), and resets the
        per-replica timing listener — its device list is stale."""
        from deeplearning4j_tpu.parallel.meshtrainer import (MeshTrainer,
                                                             ShardingPlan)
        self.mesh = mesh
        plan = ShardingPlan.for_model(self.model, mesh,
                                      tensorParallel=self.tensorParallel)
        if self._trainer is not None and self._trainer.net is self.model:
            self._trainer.remesh(plan, reshard=reshard)
        else:
            self._trainer = MeshTrainer(self.model, plan=plan)
        self._replicaTimer = None
        get_registry().gauge(
            "dl4j_tpu_parallel_replicas",
            "Devices participating in the data-parallel mesh").set(
                mesh.numDevices())

    # -- API -------------------------------------------------------------
    def fit(self, iterator, epochs: int = 1) -> None:
        """Train with batches sharded across the mesh's data axis.

        All mesh shapes route through ``MeshTrainer``'s single jitted
        step: a ``stage`` axis trains the model's pipelineStages segments
        GPipe-scheduled behind the same surface, a ``seq`` axis makes the
        attention layers compile ring (context-parallel) attention, and
        DP/TP/ZeRO-1/EP compose inside the one executable — all through
        the dl4j-shaped model config, no user JAX."""
        # streaming sources engage the sharded producer pool here (not in
        # net.fit) so the GPipe pipeline path overlaps host ETL too; the
        # wrapper owns the pool's close().  Prefetch H2D staging routes
        # through the plan's batch sharding so sharded inputs land
        # directly on their mesh shards instead of replicated-then-
        # resharded (stage meshes consume on host and keep plain staging).
        from deeplearning4j_tpu.datavec.pipeline import maybe_prefetch
        tr = self.trainer()
        device = tr.plan.batch_sharding() \
            if self.mesh.dataSize > 1 and self.mesh.stageSize == 1 else None
        src = iterator
        if device is not None and hasattr(iterator, "setDevice"):
            # a caller-built AsyncDataSetIterator gets the same
            # direct-to-shard H2D routing as the producer pool
            iterator.setDevice(device)
        iterator = maybe_prefetch(iterator, device=device)
        try:
            self._fit_inner(iterator, epochs)
        finally:
            if iterator is not src:
                iterator.close()

    def _fit_inner(self, iterator, epochs: int) -> None:
        tr = self.trainer()
        if self.mesh.stageSize > 1:
            tr.fit(iterator, epochs=epochs)
            return
        net = self.model
        timer = self._timing()
        net.addListeners(timer)
        try:
            with tracer().span("dp_fit", replicas=int(self.mesh.dataSize),
                               epochs=int(epochs)):
                tr.fit(iterator, epochs=epochs)
        finally:
            net.removeListener(timer)

    def _timing(self) -> ReplicaTimingListener:
        """Persistent straggler/contention watcher for this wrapper's mesh:
        per-replica lockstep step-time gauges + the rolling max/min spread
        (``dl4j_tpu_parallel_step_time_spread``; above 2.0 the window was
        contended)."""
        if getattr(self, "_replicaTimer", None) is None:
            devices = list(self.mesh.mesh.devices.flat)
            self._replicaTimer = ReplicaTimingListener(devices)
            get_registry().gauge(
                "dl4j_tpu_parallel_replicas",
                "Devices participating in the data-parallel mesh").set(
                    len(devices))
        return self._replicaTimer

    def healthRules(self, stragglerRatio: float = 2.0):
        """Watchdog rules scoped to THIS wrapper's mesh: the per-replica
        straggler check over the step-time gauges the wrapper's
        ``ReplicaTimingListener`` publishes.  ``SharedTrainingMaster``
        composes these with the run-level stall/starvation/divergence
        rules when it builds the fit's HealthMonitor; callers running the
        wrapper directly can do the same::

            HealthMonitor(rules=default_rules() + wrapper.healthRules())
        """
        from deeplearning4j_tpu.telemetry.health import ReplicaStragglerRule
        self._timing()      # ensure the replica gauges exist to watch
        return [ReplicaStragglerRule(ratio=stragglerRatio)]

    def fitDataSet(self, ds) -> None:
        """One train step on a single batch — the FaultTolerantTrainer's
        per-batch entry point (it owns the epoch loop, checkpoint cadence,
        and rollback, so it needs step-level granularity the
        iterator-driven ``fit`` can't give it).  EVERY mesh shape steps
        here through ``MeshTrainer.step`` — data/tensor/sequence/expert
        axes compile into the one sharded executable, a stage axis runs
        the GPipe schedule behind the same surface."""
        tr = self.trainer()
        t0 = time.perf_counter()
        with tracer().span("dp_step", replicas=int(self.mesh.dataSize)):
            tr.step(ds)
        self._timing().record(time.perf_counter() - t0)

    # -- supervision hooks (driven by FaultTolerantTrainer) ---------------
    def syncToNet(self) -> None:
        """Flush trainer-held state (stage meshes: the stacked GPipe
        rows) back into the net's trees before a checkpoint."""
        if self._trainer is not None:
            self._trainer.syncToNet()

    def placeAfterRestore(self) -> None:
        """Re-assert plan placement after a checkpoint restore."""
        self.trainer().placeAfterRestore()

    def shutdown(self) -> None:
        pass
