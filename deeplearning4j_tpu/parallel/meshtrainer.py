"""One GSPMD mesh: the unified sharded train step for all of ``parallel/``.

Before this module the parallel layer was six coexisting stepping paths
(wrapper, sharedtraining, pipeline, ring, zero, moe), so sharding
strategies could not compose and fault supervision could not step
pipeline/seq meshes (ROADMAP item 3).  The fix is the GSPMD pattern the
paper's TPU mapping endorses (SNIPPETS [3], the pjit machinery of
[1]/[2]): describe WHERE every tensor lives with a
:class:`~jax.sharding.NamedSharding` over ONE named-axis mesh and let
XLA insert the collectives — including the sharded weight update of
PAPERS arXiv:2004.13336 (ZeRO-1) — instead of hand-rolling per-strategy
exchange.

Two classes:

- :class:`ShardingPlan` — the placement contract: per-param and
  per-optimizer-state ``PartitionSpec``s over the existing
  :class:`~deeplearning4j_tpu.parallel.mesh.DeviceMesh` axes
  (``data``/``model``/``seq``/``stage``, with ``model`` doubling as the
  expert axis for MoE), the batch sharding, and the activation
  constraint applied inside the traced step.
- :class:`MeshTrainer` — compiles ONE jitted donated train step for the
  wrapped model with explicit in/out shardings derived from the plan,
  so DP x TP x ZeRO-1 x EP compose inside a single executable.  The old
  entry points (``ParallelWrapper``, ``SharedTrainingMaster``,
  ``zero.ZeroStage1``, MoE fits) are thin facades over it, and
  ``FaultTolerantTrainer`` supervises every mesh shape through
  :meth:`MeshTrainer.step` — including ``stage`` meshes, which delegate
  to the GPipe :class:`~deeplearning4j_tpu.parallel.pipeline_model.
  PipelinedTrainer` behind the same ``step()``/sync surface.

Telemetry: the ``dl4j_tpu_mesh_*`` namespace (registered once in
``telemetry.instrument.MeshMetrics``) — step time, per-axis collective
bytes estimated statically from the plan, and jit cache misses (flat
after step 1 is the steady-state acceptance bar).
"""
from __future__ import annotations

import inspect
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.multilayer import (_iter_leaf_params,
                                                  _set_leaf)
from deeplearning4j_tpu.parallel.mesh import (DeviceMesh, activate_mesh,
                                              _dense_tp_spec)
from deeplearning4j_tpu.parallel.zero import _leaf_spec

__all__ = ["ShardingPlan", "MeshTrainer", "active_plan", "activate_plan",
           "reshard_tree", "apply_inference_plan", "place_replica"]


def _identity(tree):
    return tree


def reshard_tree(tree, shardings):
    """Plan-to-plan reshard: move a pytree onto ``shardings`` device-side.

    Two lowerings, both free of a host round-trip:

    - **same device set** (the plan changed but the chips didn't — e.g.
      a TP/ZeRO layout change, or an axis refactorization over the same
      slice): ONE jitted identity executable with explicit
      ``out_shardings`` — GSPMD lowers the move to pure on-device
      collective gather/scatter, and the donated input buffers are
      aliased or freed as each leaf lands;
    - **different device sets** (elastic shrink/grow: chips left or
      joined): ``jax.device_put`` onto the target shardings, which XLA
      services with device-to-device copies where the runtime supports
      them.

    A deliberate re-mesh compiles a fresh executable by design — that
    is the cost of changing the mesh, paid once per re-mesh, not per
    step."""
    if tree is None:
        return None
    leaves = jax.tree_util.tree_leaves(tree)
    if leaves and all(hasattr(leaf, "sharding") for leaf in leaves):
        src = set()
        for leaf in leaves:
            src |= set(leaf.sharding.device_set)
        dst = set()
        for sh in jax.tree_util.tree_leaves(shardings):
            dst |= set(sh.device_set)
        if src == dst:
            try:
                # jaxlint: disable=retrace-closure -- a re-mesh IS a one-shot recompile by design: new shardings => new executable, paid once per re-mesh, never per step
                return jax.jit(_identity, out_shardings=shardings,
                               donate_argnums=0)(tree)
            except Exception:
                # an out_shardings the compiler rejects (uncommitted
                # inputs, odd layouts) still reshards correctly below
                pass
    # jaxlint: disable=donation-use-after -- the only donating call is
    # the jit dispatch above, and it can only raise at COMPILE time,
    # before any buffer is consumed; a successful dispatch returns, so
    # this line never sees a donated-and-freed tree
    return jax.device_put(tree, shardings)


#: executables a raw-params model (TransformerLM-style) caches in its
#: __dict__ — every inference-mode re-placement must pop these: JAX's
#: jaxpr cache keys on function identity + avals (NOT shardings), so a
#: reused closure would resurrect the previous placement's trace
_INFERENCE_CACHE_KEYS = ("_fwd", "_prefillRawFn",
                         "_outputFn", "_scoreFn", "_trainStep")


def _pop_inference_caches(model) -> None:
    for k in _INFERENCE_CACHE_KEYS:
        model.__dict__.pop(k, None)


def apply_inference_plan(model, plan: "ShardingPlan"):
    """Inference-mode plan application — the serving tier's TP replica
    path (ROADMAP item 1): place a raw-params model's weight pytree
    (``model.params``, TransformerLM-style) onto ``plan``'s mesh and
    drop its cached executables so the next dispatch traces against the
    new placement.

    Under tensor parallelism every 2D weight whose last dim divides the
    model axis column-shards (the serving analogue of the training TP
    rule); everything else replicates.  Committed input shardings are
    all GSPMD needs — the jitted prefill/decode executables partition
    themselves and insert the collectives, so a model too big for one
    chip serves over several with no code change above this call.
    Returns the model.
    """
    tp = plan.tensorParallel
    jmesh = plan.mesh.mesh
    msize = plan.mesh.modelSize
    axis = plan.modelAxis

    def sh(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if tp and msize > 1 and len(shape) == 2 and shape[1] % msize == 0:
            return NamedSharding(jmesh, P(None, axis))
        return NamedSharding(jmesh, P())

    model.params = jax.device_put(model.params,
                                  jax.tree.map(sh, model.params))
    _pop_inference_caches(model)
    return model


def place_replica(model, device):
    """DP replica placement: pin a raw-params model's weights to ONE
    device (its executables then dispatch entirely on that chip — the
    small-model fan-out where each replica owns a whole copy) and drop
    cached executables.  Returns the model."""
    model.params = jax.device_put(
        model.params, jax.sharding.SingleDeviceSharding(device))
    _pop_inference_caches(model)
    return model


#: the ShardingPlan the enclosing MeshTrainer step is compiling against —
#: a TRACE-time routing signal, mirroring mesh.active_mesh(): the model
#: forward consults it to place with_sharding_constraint on activations.
_ACTIVE_PLAN: Optional["ShardingPlan"] = None


def active_plan() -> Optional["ShardingPlan"]:
    """The ShardingPlan of the enclosing MeshTrainer step, if any
    (consulted at trace time by the model ``_forward`` loops)."""
    return _ACTIVE_PLAN


class activate_plan:
    """Context manager marking ``plan`` active for activation sharding."""

    def __init__(self, plan: Optional["ShardingPlan"]):
        self.plan = plan

    def __enter__(self):
        global _ACTIVE_PLAN
        self._prev = _ACTIVE_PLAN
        _ACTIVE_PLAN = self.plan
        return self.plan

    def __exit__(self, *exc):
        global _ACTIVE_PLAN
        _ACTIVE_PLAN = self._prev
        return False


def _units(net):
    """``(key, layer)`` pairs for a MultiLayerNetwork (index keys) or a
    ComputationGraph (node-name keys) — the shared addressing of
    ``params_``/``optState_``."""
    conf = net.conf
    if hasattr(conf, "layers"):
        return [(str(i), layer) for i, layer in enumerate(conf.layers)]
    return [(name, conf.nodes[name][0]) for name in conf.topoOrder]


class ShardingPlan:
    """Per-tensor ``PartitionSpec``s over one named-axis DeviceMesh.

    The placement rules compose:

    - batch arrays shard dim 0 over ``data`` (DP);
    - with ``tensorParallel``, 2D weights column-shard and their biases
      shard over ``model`` (TP) when divisible;
    - expert layers (``expertParamKeys``) shard their leading expert dim
      over ``model`` (EP — ``model`` doubles as the expert axis);
    - embedding tables (``rowShardedParamKeys``) row-shard their leading
      dim over ``model`` (table parallelism — the recommender tier's
      too-big-for-one-device tables), moments following the rows;
    - with ``zero1``, optimizer-state leaves shard their largest
      divisible dim over ``data`` (the arXiv:2004.13336 sharded weight
      update: gradients reduce-scatter into the sharded updater math,
      updated params all-gather back — all inserted by GSPMD);
    - everything else replicates, and ``seq``/``stage`` axes route
      through the mesh activation (ring attention / GPipe).
    """

    def __init__(self, mesh: DeviceMesh, tensorParallel: bool = False,
                 zero1: bool = False, dataAxis: str = "data",
                 modelAxis: str = "model", zeroAxis: str = "data"):
        self.mesh = mesh
        self.tensorParallel = bool(tensorParallel)
        self.zero1 = bool(zero1)
        self.dataAxis = dataAxis
        self.modelAxis = modelAxis
        self.zeroAxis = zeroAxis

    # -- construction ---------------------------------------------------
    @classmethod
    def for_model(cls, net, mesh: DeviceMesh,
                  tensorParallel: bool = False) -> "ShardingPlan":
        """Plan for ``net`` on ``mesh``, honouring a ZeRO-1 tag left by
        :class:`~deeplearning4j_tpu.parallel.zero.ZeroStage1`."""
        zeroAxis = getattr(net, "_zero1Axis", None)
        return cls(mesh, tensorParallel=tensorParallel,
                   zero1=zeroAxis is not None,
                   zeroAxis=zeroAxis or "data")

    # -- specs ----------------------------------------------------------
    def param_spec(self, layer, pname: str, shape: Tuple[int, ...]) -> P:
        msize = self.mesh.modelSize
        if msize > 1:
            ekeys = getattr(layer, "expertParamKeys", None)
            if ekeys is not None and pname in ekeys() and shape \
                    and shape[0] % msize == 0:
                # EP: leading expert dim over the model axis — each
                # device group materializes only its own experts
                return P(self.modelAxis)
            rkeys = getattr(layer, "rowShardedParamKeys", None)
            if rkeys is not None and pname in rkeys() and shape \
                    and shape[0] % msize == 0:
                # table-parallel embeddings: rows over the model axis;
                # opt_shardings mirrors the spec onto the Adam moments,
                # so the optimizer rows shard alongside the table
                return P(self.modelAxis)
            if self.tensorParallel:
                spec = _dense_tp_spec(pname, shape, self.modelAxis)
                dims = [d for d, ax in enumerate(spec) if ax is not None]
                if all(shape[d] % msize == 0 for d in dims):
                    return spec
        return P()

    def param_shardings(self, net):
        """NamedSharding pytree matching ``net.params_`` exactly."""
        jmesh = self.mesh.mesh
        out: Dict = {}
        for key, layer in _units(net):
            if key not in (net.params_ or {}):
                continue
            out[key] = {}
            for path, pname, val in _iter_leaf_params(net.params_[key]):
                spec = self.param_spec(layer, pname, tuple(val.shape))
                _set_leaf(out[key], path,
                          NamedSharding(jmesh, spec))
        return out

    def opt_shardings(self, net):
        """NamedSharding pytree matching ``net.optState_``.

        Moment tensors mirror their param's shape, so a TP/EP-sharded
        param's updater state carries the SAME spec (the memory win
        extends to the optimizer); replicated params' state shards its
        largest divisible dim over the data axis under ZeRO-1; scalars
        and odd shapes replicate.  Explicit placement here is what keeps
        the donated opt buffers reusable and the executable cache flat —
        propagation-chosen shardings would differ from the committed
        inputs on step 2 and retrace."""
        if net.optState_ is None:
            return None
        jmesh = self.mesh.mesh
        zsize = jmesh.shape.get(self.zeroAxis, 1) if self.zero1 else 1
        out: Dict = {}
        for key, layer in _units(net):
            if key not in net.optState_:
                continue
            pmap = {path: (pname, tuple(val.shape))
                    for path, pname, val
                    in _iter_leaf_params((net.params_ or {}).get(key, {}))}
            out[key] = {}
            for path, sub in net.optState_[key].items():
                pname, pshape = pmap.get(path, (None, None))
                pspec = self.param_spec(layer, pname, pshape) \
                    if pname is not None else P()

                def leaf_sh(leaf, _pspec=pspec, _pshape=pshape):
                    shape = tuple(getattr(leaf, "shape", ()))
                    if not shape:
                        return NamedSharding(jmesh, P())
                    if tuple(_pspec) and shape == _pshape:
                        return NamedSharding(jmesh, _pspec)
                    if self.zero1:
                        return NamedSharding(
                            jmesh, _leaf_spec(leaf, self.zeroAxis, zsize))
                    return NamedSharding(jmesh, P())

                out[key][path] = jax.tree.map(leaf_sh, sub)
        return out

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh.mesh, P(self.dataAxis))

    def constrain(self, x):
        """``with_sharding_constraint`` pinning the batch dim of an
        activation over the data axis — applied inside the traced step
        so GSPMD anchors the layout between layers instead of
        re-deriving it per op.  No-op for non-divisible/scalar shapes."""
        if self.mesh.dataSize <= 1:
            return x
        shape = getattr(x, "shape", None)
        if not shape or shape[0] % self.mesh.dataSize != 0:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh.mesh, P(self.dataAxis)))

    # -- accounting -----------------------------------------------------
    def axis_sizes(self) -> Dict[str, int]:
        m = self.mesh
        return {"data": m.dataSize, "model": m.modelSize,
                "seq": m.seqSize, "stage": m.stageSize}

    def collective_bytes_per_step(self, net) -> Dict[Tuple[str, str], int]:
        """Static per-step collective traffic estimate from the plan:
        ``(axis, collective) -> bytes``.

        Model (ring algorithms, f32 leaves): a param leaf REPLICATED
        across an axis of size ``s`` costs a gradient all-reduce of
        ``2*(s-1)/s * nbytes`` on that axis; under ZeRO-1 the data-axis
        all-reduce splits into a reduce-scatter plus a param all-gather
        of ``(s-1)/s * nbytes`` each (same wire bytes, half the peak
        buffer).  Leaves sharded over an axis (TP/EP) pay nothing on it
        — their gradient segments stay local.  An estimate, not a
        measurement: it prices the PLAN so regressions in placement show
        up before a profiler run does.
        """
        out: Dict[Tuple[str, str], int] = {}

        def add(axis, coll, nbytes):
            key = (axis, coll)
            out[key] = int(out.get(key, 0) + nbytes)

        zsize = self.mesh.mesh.shape.get(self.zeroAxis, 1)
        for key, layer in _units(net):
            if key not in (net.params_ or {}):
                continue
            if getattr(layer, "frozen", False):
                continue
            for _path, pname, val in _iter_leaf_params(net.params_[key]):
                shape = tuple(val.shape)
                nbytes = int(np.prod(shape)) * val.dtype.itemsize
                spec_axes = {ax for ax in
                             self.param_spec(layer, pname, shape)
                             if ax is not None}
                for axis, size in self.axis_sizes().items():
                    if size <= 1 or axis == "stage" or axis in spec_axes:
                        continue
                    frac = (size - 1) / size
                    if self.zero1 and axis == self.zeroAxis and \
                            len(_leaf_spec(val, self.zeroAxis, zsize)) > 0:
                        add(axis, "reduce_scatter", frac * nbytes)
                        add(axis, "all_gather", frac * nbytes)
                    else:
                        add(axis, "all_reduce", 2 * frac * nbytes)
        return out

    def __repr__(self):
        return (f"ShardingPlan({self.mesh!r}, tp={self.tensorParallel}, "
                f"zero1={self.zero1})")


class MeshTrainer:
    """The one stepping path for every mesh shape.

    Compiles the model's raw step function (``net._stepFn`` — the exact
    fused fwd+bwd+updater computation the model itself jits) as ONE
    donated executable with the plan's in/out shardings, installs it as
    the model's ``_trainStep`` (so `net.fit`'s TBPTT chunking, OOM
    micro-batch retry, listeners and telemetry all ride it unchanged),
    and exposes:

    - :meth:`step` — one supervised-grade train step on a DataSet (the
      ``FaultTolerantTrainer`` per-batch entry for EVERY mesh shape);
    - :meth:`fit` — iterator/epochs training through the same
      executable;
    - :meth:`syncToNet` / :meth:`placeAfterRestore` — the checkpoint
      hooks the fault supervisor drives (stage meshes write their
      stacked GPipe rows back into the net's per-layer trees here).

    ``stage`` meshes delegate the step math to the GPipe
    ``PipelinedTrainer`` but keep this class's surface, telemetry and
    supervision contract — one code path above, two lowerings below.
    """

    def __init__(self, model, plan: Optional[ShardingPlan] = None,
                 mesh: Optional[DeviceMesh] = None,
                 tensorParallel: bool = False):
        self.net = model
        if plan is None:
            plan = ShardingPlan.for_model(model, mesh or DeviceMesh(),
                                          tensorParallel=tensorParallel)
        self.plan = plan
        self._jit = None
        self._jitKey = None          # params treedef the jit was built for
        self._pipeline = None
        self._pipeline_src = None
        self._bytes = None           # cached per-step collective estimate
        self._stepsSeen = 0

    # -- placement ------------------------------------------------------
    def _needs_place(self) -> bool:
        net = self.net
        if net.params_ is None:
            return True
        leaves = jax.tree_util.tree_leaves(net.params_)
        if not leaves:
            return True
        leaf = leaves[0]
        return not (hasattr(leaf, "sharding") and
                    set(leaf.sharding.device_set) ==
                    set(self.plan.mesh.mesh.devices.flat))

    def place(self) -> None:
        """Place params/optimizer state per the plan.  Cheap no-op in the
        steady state (the jitted step's out_shardings keep everything in
        place); re-runs after init or a checkpoint restore landed arrays
        somewhere else."""
        net = self.net
        if net.params_ is None:
            net.init()
        psh = self.plan.param_shardings(net)
        net.params_ = jax.device_put(net.params_, psh)
        osh = self.plan.opt_shardings(net)
        if net.optState_ is not None and osh is not None:
            net.optState_ = jax.device_put(net.optState_, osh)
        if getattr(net, "state_", None):
            # aux layer state (BatchNorm running stats) is replicated, and
            # committed here: left where init put it, the first step would
            # compile for that placement and the second for the one the
            # step's outputs came back with
            net.state_ = jax.device_put(net.state_,
                                        self.plan.mesh.replicated())

    # -- compilation ----------------------------------------------------
    def _install(self) -> None:
        """Build the plan-sharded jitted step and install it as the
        net's ``_trainStep`` so every fit path (plain, TBPTT, OOM retry)
        dispatches THIS executable.  The net's ``_ensure_trace_mesh``
        drops it again when the net is later used outside any mesh."""
        net = self.net
        psh = self.plan.param_shardings(net)
        osh = self.plan.opt_shardings(net)
        nargs = len(inspect.signature(net._stepFn).parameters)
        rep = self.plan.mesh.replicated()
        in_sh = [None] * nargs
        in_sh[0], in_sh[1], in_sh[2] = psh, osh, rep
        jitted = jax.jit(net._stepFn, donate_argnums=(0, 1, 2),
                         in_shardings=tuple(in_sh),
                         out_shardings=(psh, osh, rep, None, None))
        # AOT cache (when configured): the sharded step dispatches
        # through the persistent executable cache, keyed on THIS plan's
        # digest + device set — so a boot (or post-remesh re-install)
        # preloads warm executables, and a stale pre-remesh executable
        # can never key-match the new plan.  Plain jit when off.
        from deeplearning4j_tpu.compile.aotcache import wrap_jit
        jitted = wrap_jit(jitted, kind="mesh_step", model=net,
                          plan=self.plan)
        for k in ("_trainStep", "_outputFn", "_scoreFn"):
            net.__dict__.pop(k, None)
        net.__dict__["_trainStep"] = jitted
        net._meshTrace = self.plan
        self._jit = jitted
        self._jitKey = jax.tree_util.tree_structure(net.params_)
        from deeplearning4j_tpu.telemetry import mesh_metrics
        g = mesh_metrics().axis_size()
        for axis, size in self.plan.axis_sizes().items():
            g.set(size, axis=axis)

    def _ensure_ready(self) -> None:
        net = self.net
        if net.params_ is None:
            net.init()
        if self.plan.mesh.stageSize > 1:
            self._ensure_pipeline()
            return
        if self._needs_place():
            self.place()
        if self._jit is None or net.__dict__.get("_trainStep") \
                is not self._jit or \
                self._jitKey != jax.tree_util.tree_structure(net.params_):
            self._install()

    def _ensure_pipeline(self) -> None:
        # rebuild when the net's params dict was REPLACED (net.init() or
        # a restored checkpoint) — the stacked copy would otherwise
        # silently overwrite the new weights on write-back
        if self._pipeline is None or \
                self._pipeline_src is not self.net.params_:
            from deeplearning4j_tpu.parallel.pipeline_model import \
                PipelinedTrainer
            self._pipeline = PipelinedTrainer(self.net, self.plan.mesh)
            self._pipeline_src = self.net.params_

    def jitCacheSize(self) -> int:
        fn = self.net.__dict__.get("_trainStep") \
            if self.plan.mesh.stageSize == 1 \
            else getattr(self._pipeline, "_step", None)
        if fn is None:
            return 0
        try:
            return int(fn._cache_size())
        except Exception:
            return 0

    # -- telemetry ------------------------------------------------------
    def _per_step_bytes(self) -> Dict[Tuple[str, str], int]:
        if self._bytes is None:
            self._bytes = self.plan.collective_bytes_per_step(self.net)
        return self._bytes

    def _record(self, steps: int, seconds: float, misses: int) -> None:
        if steps <= 0:
            return
        from deeplearning4j_tpu.telemetry import mesh_metrics
        from deeplearning4j_tpu.telemetry.instrument import observe_exemplar
        from deeplearning4j_tpu.telemetry.runlog import current_run
        mm = mesh_metrics()
        mm.steps().inc(steps)
        # ensure registration, then observe through the exemplar path so
        # a p99 mesh-step spike links to one (trace id, generation, step)
        mm.step_seconds()
        rc = current_run()
        observe_exemplar(
            "dl4j_tpu_mesh_step_seconds", seconds / steps,
            rc.runId if rc is not None else None,
            attrs=None if rc is None else {
                # jaxlint: sync-ok -- run generation is a host-side Python counter
                "generation": int(rc.generation),
                # jaxlint: sync-ok -- iterationCount is a host-side Python counter
                "step": int(self.net.iterationCount)})
        if misses > 0:
            mm.jit_cache_misses().inc(misses)
        cb = mm.collective_bytes()
        for (axis, coll), nbytes in self._per_step_bytes().items():
            cb.inc(nbytes * steps, axis=axis, collective=coll)
        self._stepsSeen += steps

    # -- stepping -------------------------------------------------------
    def step(self, ds) -> None:
        """One train step on a single batch through the unified sharded
        executable — the fault supervisor's per-batch entry point for
        EVERY mesh shape (data/model/seq/zero/expert axes compile into
        the one jitted step; a stage axis delegates to the GPipe
        schedule behind the same surface)."""
        net = self.net
        self._ensure_ready()
        misses0 = self.jitCacheSize()
        t0 = time.perf_counter()
        if self.plan.mesh.stageSize > 1:
            self._pipeline.fitDataSet(ds)
        else:
            net.setBatchSharding(self.plan.batch_sharding())
            try:
                with activate_mesh(self.plan.mesh), activate_plan(self.plan):
                    net.fit(ds)
            finally:
                net.setBatchSharding(None)
        self._record(1, time.perf_counter() - t0,
                     self.jitCacheSize() - misses0)

    def fit(self, iterator, epochs: int = 1) -> None:
        """Iterator training through the same installed executable (the
        model's own epoch loop, listeners, TBPTT and telemetry all run
        unchanged — they just dispatch the plan-sharded step)."""
        net = self.net
        self._ensure_ready()
        if self.plan.mesh.stageSize > 1:
            it0 = net.iterationCount
            misses0 = self.jitCacheSize()
            t0 = time.perf_counter()
            self._pipeline.fit(iterator, epochs=epochs)
            self._record(net.iterationCount - it0,
                         time.perf_counter() - t0,
                         self.jitCacheSize() - misses0)
            return
        it0 = net.iterationCount
        misses0 = self.jitCacheSize()
        t0 = time.perf_counter()
        net.setBatchSharding(self.plan.batch_sharding())
        try:
            with activate_mesh(self.plan.mesh), activate_plan(self.plan):
                net.fit(iterator, epochs=epochs)
        except BaseException:
            # don't leave half-compiled mesh-bound traces behind
            for k in ("_trainStep", "_outputFn", "_scoreFn"):
                net.__dict__.pop(k, None)
            net._meshTrace = None
            self._jit = None
            raise
        finally:
            net.setBatchSharding(None)
        self._record(net.iterationCount - it0, time.perf_counter() - t0,
                     self.jitCacheSize() - misses0)

    # -- elastic re-mesh ------------------------------------------------
    def remesh(self, plan: ShardingPlan, reshard: bool = True) -> None:
        """Adopt a new :class:`ShardingPlan` (elastic shrink/grow or a
        deliberate layout change) and invalidate the installed
        executable so the next step compiles against the new mesh.

        ``reshard=True`` moves the LIVE params/optimizer state onto the
        new plan's shardings via :func:`reshard_tree` (device-side; the
        grow / straggler-eviction path, where the training state is
        intact).  ``reshard=False`` only swaps the plan — the caller is
        about to restore a sealed checkpoint directly INTO the new
        placement (the shrink-on-device-loss path, where the state that
        died mid-step cannot be trusted)."""
        net = self.net
        self.plan = plan
        self._bytes = None
        self._pipeline = None
        self._pipeline_src = None
        if reshard and net.params_ is not None \
                and plan.mesh.stageSize == 1:
            net.params_ = reshard_tree(net.params_,
                                       plan.param_shardings(net))
            osh = plan.opt_shardings(net)
            if net.optState_ is not None and osh is not None:
                net.optState_ = reshard_tree(net.optState_, osh)
            # EVERY step input must land on the new device set or the
            # jitted step mixes device assignments: aux layer state, the
            # training RNG key and rnn carries are replicated, so a
            # broadcast placement is their reshard
            rep = NamedSharding(plan.mesh.mesh, P())
            if getattr(net, "state_", None):
                net.state_ = jax.device_put(net.state_, rep)
            if getattr(net, "_fitKey", None) is not None:
                net._fitKey = jax.device_put(net._fitKey, rep)
            if getattr(net, "_rnnCarries", None):
                net._rnnCarries = jax.device_put(net._rnnCarries, rep)
        # _stepFn included: it is a cached_property, and JAX's jaxpr
        # cache keys on the underlying function identity + avals (NOT
        # shardings) — reusing the object would resurrect the OLD mesh's
        # baked-in with_sharding_constraint equations on the new mesh
        for k in ("_trainStep", "_outputFn", "_scoreFn", "_stepFn"):
            net.__dict__.pop(k, None)
        net._meshTrace = None
        self._jit = None
        self._jitKey = None

    # -- supervision hooks ----------------------------------------------
    def syncToNet(self) -> None:
        """Flush trainer-held state back into the net's per-layer trees
        before a checkpoint (stage meshes keep the live weights in
        stacked GPipe rows; every other mesh shape trains ``net.params_``
        in place, so this is free)."""
        if self._pipeline is not None:
            self._pipeline.syncToNet()
            self._pipeline_src = self.net.params_

    def placeAfterRestore(self) -> None:
        """Re-assert plan placement after a checkpoint restore dropped
        arrays on a single device (stage meshes restack their GPipe
        rows from the restored trees)."""
        if self.plan.mesh.stageSize > 1:
            if self._pipeline is not None:
                self._pipeline.reloadFromNet()
                self._pipeline_src = self.net.params_
            return
        self.place()
