"""MultiLayerNetwork — linear-stack model.

Reference: deeplearning4j-nn ``org/deeplearning4j/nn/multilayer/
MultiLayerNetwork.java`` (fit/output/evaluate/score, flattened param views,
per-iteration Solver/updater orchestration — SURVEY.md §3.1).

TPU-first design: where the reference dispatches every op across JNI and
mutates a flat param view in place, this model compiles ONE fused XLA
executable per (shape, mode): forward + loss + backward (``jax.value_and_grad``)
+ gradient normalization + updater + regularization, with params/opt-state
buffers donated.  That single-executable train step IS the north-star design
replacing op-by-op dispatch (SURVEY.md §3.1, §7.1).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.eval.evaluation import (Evaluation,
                                                RegressionEvaluation, ROC)
from deeplearning4j_tpu.learning.config import Sgd
from deeplearning4j_tpu.learning.regularization import WeightDecay
from deeplearning4j_tpu.nn.conf import (GradientNormalization,
                                        MultiLayerConfiguration)
from deeplearning4j_tpu.ops import NDArray
from deeplearning4j_tpu.optimize.listeners import notifyListeners
from deeplearning4j_tpu.profiler import check_panic, panic_enabled
from deeplearning4j_tpu.telemetry import (etl_fetch, h2d_span,
                                          in_microbatch, train_step_span)

Params = Dict[str, Dict[str, jax.Array]]

#: canonical intra-layer param order (serialization parity: DL4J's
#: flattened-view layout — input weights, recurrent weights, bias;
#: BN adds gamma/beta; GravesLSTM peepholes; Bidirectional fwd/bwd halves)
_PARAM_ORDER = ["W", "RW", "b", "gamma", "beta", "pI", "pF", "pO",
                "fwd", "bwd"]


def _param_key_order(keys):
    known = [k for k in _PARAM_ORDER if k in keys]
    rest = sorted(k for k in keys if k not in _PARAM_ORDER)
    return known + rest


def _place_batch_with(sharding, arr):
    """Place a batch array with a mesh NamedSharding (None/odd batch sizes
    pass through) — shared by MultiLayerNetwork and ComputationGraph."""
    if arr is None or sharding is None:
        return arr
    try:
        sharding.shard_shape(arr.shape)  # divisibility check
    except ValueError:
        return arr
    return jax.device_put(arr, sharding)


def _iter_leaf_params(lp: Dict, prefix: str = ""):
    """Yield ``(path, pname, value)`` over a layer's params in canonical
    order, descending into nested dicts (Bidirectional's fwd/bwd halves)."""
    for k in _param_key_order(lp.keys()):
        v = lp[k]
        if isinstance(v, dict):
            yield from _iter_leaf_params(v, prefix + k + "/")
        else:
            yield prefix + k, k, v


def _ravel_replicated(v):
    """Device-resident 1D view of a param leaf for the flat-vector API.

    Mesh-sharded leaves reshard to replicated FIRST: the flat vector is
    a logical (unsharded) object, and eager ``jnp.concatenate`` over
    mixed-sharded inputs miscompiles on some backends (observed on the
    CPU host-platform mesh: stride-pattern garbage).  The reshard is an
    on-device all-gather, not a host sync."""
    sh = getattr(v, "sharding", None)
    if sh is not None and hasattr(sh, "spec") and \
            not sh.is_fully_replicated:
        from jax.sharding import NamedSharding, PartitionSpec
        v = jax.device_put(v, NamedSharding(sh.mesh, PartitionSpec()))
    return jnp.ravel(v)


def _constrain_act(x):
    """Anchor an activation's layout when a MeshTrainer plan is active
    (trace-time, like ``mesh.active_mesh``): ``with_sharding_constraint``
    pins the batch dim over the data axis so GSPMD keeps one layout
    between layers instead of re-deriving it per op."""
    from deeplearning4j_tpu.parallel.meshtrainer import active_plan
    plan = active_plan()
    return x if plan is None else plan.constrain(x)


def _get_leaf(d: Dict, path: str):
    for p in path.split("/"):
        d = d[p]
    return d


def _set_leaf(d: Dict, path: str, value) -> None:
    parts = path.split("/")
    for p in parts[:-1]:
        d = d.setdefault(p, {})
    d[parts[-1]] = value


def _grad_normalize(layer, g):
    """Per-layer gradient normalization (reference:
    ``BaseMultiLayerUpdater.preApply``).  Tree-aware: ``g`` may contain
    nested dicts (Bidirectional)."""
    mode = getattr(layer, "gradientNormalization", None)
    if not mode or mode == GradientNormalization.None_:
        return g
    thr = getattr(layer, "gradientNormalizationThreshold", None) or 1.0
    tm = jax.tree_util.tree_map

    def layer_norm():
        return jnp.sqrt(sum(jnp.sum(v * v)
                            for v in jax.tree_util.tree_leaves(g)) + 1e-12)

    if mode == GradientNormalization.RenormalizeL2PerLayer:
        norm = layer_norm()
        return tm(lambda v: v / norm, g)
    if mode == GradientNormalization.RenormalizeL2PerParamType:
        return tm(lambda v: v / jnp.sqrt(jnp.sum(v * v) + 1e-12), g)
    if mode == GradientNormalization.ClipElementWiseAbsoluteValue:
        return tm(lambda v: jnp.clip(v, -thr, thr), g)
    if mode == GradientNormalization.ClipL2PerLayer:
        scale = jnp.minimum(1.0, thr / layer_norm())
        return tm(lambda v: v * scale, g)
    if mode == GradientNormalization.ClipL2PerParamType:
        return tm(lambda v: v * jnp.minimum(
            1.0, thr / jnp.sqrt(jnp.sum(v * v) + 1e-12)), g)
    raise ValueError(f"Unknown gradient normalization {mode}")


def _updater_for(globalConf, layer, pname: str):
    """Effective updater for one param (shared by MLN and ComputationGraph)."""
    if pname == "b" and getattr(layer, "biasUpdater", None) is not None:
        return layer.biasUpdater
    return getattr(layer, "updater", None) or globalConf.get("updater") \
        or Sgd(1e-2)


def _apply_updates(units, globalConf, params, grads, optState, iteration,
                   epoch, lrScale=None):
    """Apply updaters over all trainable leaves (per-leaf math).

    ``units`` is an iterable of ``(key, layer)`` — MLN layer indices or
    ComputationGraph node names.  Frozen layers pass through untouched;
    layers with per-layer gradient normalization get their norms over
    exactly their own leaves.  Returns ``(new_params, new_opt)``.

    Perf note (measured, v5e, ResNet-50 bf16 B=256): concatenating leaves
    that share an updater config into one flat vector — the reference's
    flattened-view design (``BaseMultiLayerUpdater`` over
    ``paramsFlattened``) — was tried and is ~50 ms/step SLOWER than this
    per-leaf form: XLA keeps conv weights in conv-friendly tiled layouts,
    and the concat/split forces a layout-normalization copy of every
    param/grad/updater-state tensor.  Per-leaf updates fuse into ~2 small
    kernels per tensor and leave layouts alone.
    """
    new_params: Dict = {}
    new_opt: Dict = {}
    for key, layer in units:
        if key not in params:
            continue
        if getattr(layer, "frozen", False):
            # Transfer learning (reference: FrozenLayer) — params and updater
            # state pass through; XLA dead-code-eliminates the unused grads.
            new_params[key] = params[key]
            new_opt[key] = optState[key]
            continue
        new_params[key] = {}
        new_opt[key] = {}
        g = _grad_normalize(layer, grads[key])
        for path, pname, pval in _iter_leaf_params(params[key]):
            up = _updater_for(globalConf, layer, pname)
            lr = up.currentLr(iteration, epoch)
            update, ostate = up.apply(_get_leaf(g, path),
                                      optState[key][path], lr,
                                      iteration, epoch, param=pval)
            wd = getattr(layer, "weightDecay", None)
            if wd and pname in layer.weightParamKeys():
                update = WeightDecay(coeff=wd).apply(pval, update, lr)
            if lrScale is not None:
                # global LR multiplier (fault supervisor's rollback
                # backoff) — traced data, so changing it never recompiles
                update = update * lrScale
            _set_leaf(new_params[key], path, pval - update)
            new_opt[key][path] = ostate
    return new_params, new_opt


def _reg_penalty(pairs):
    """L1/L2 penalty over (layer, layer_params) pairs — added to the loss
    (equivalent gradient to the reference's BEFORE_UPDATER modification)."""
    total = 0.0
    for layer, lp in pairs:
        l1 = getattr(layer, "l1", None)
        l2 = getattr(layer, "l2", None)
        if not l1 and not l2:
            continue
        wkeys = layer.weightParamKeys()
        for _path, pname, w in _iter_leaf_params(lp):
            if pname in wkeys:
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(w * w)
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(w))
    return total


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params_: Optional[Params] = None
        self.state_: Dict[str, Dict[str, jax.Array]] = {}
        self.optState_: Optional[Dict] = None
        self.iterationCount = 0
        self.epochCount = 0
        self.lastBatchSize = 0
        self._score = 0.0
        self._scoreArr = None  # pending async device-scalar loss
        self._listeners: List = []
        self._rngSeed = int(conf.globalConf.get("seed", 123) or 123)
        self._dtype = jnp.float32
        # Mixed precision (reference: .dataType(DataType.HALF/BFLOAT16) in
        # the config builder): compute in bf16 on the MXU, keep f32 master
        # params/opt-state/BN-statistics — grads flow through the casts.
        dt = str(conf.globalConf.get("dataType") or "FLOAT").upper()
        self._computeDtype = jnp.bfloat16 \
            if dt in ("BFLOAT16", "HALF", "FLOAT16") else jnp.float32
        self._fitKey = jax.random.PRNGKey(self._rngSeed ^ 0x5EED)
        self._rnnCarries = None  # rnnTimeStep stateMap (per RNN layer idx)
        self._batchSharding = None  # set by ParallelWrapper (DP over mesh)
        self._lrScale = 1.0  # FaultTolerantTrainer's divergence backoff

    def setLrScale(self, scale: float) -> None:
        """Global multiplier on every updater's step size (the fault
        supervisor's rollback backoff knob).  Enters the compiled step as
        traced data — changing it does NOT retrace.  No effect on the
        legacy line-search solvers (they pick their own step length)."""
        # jaxlint: disable=host-sync -- scale is a host config scalar from the supervisor
        self._lrScale = float(scale)

    def getLrScale(self) -> float:
        return self._lrScale

    def setBatchSharding(self, sharding) -> None:
        """Shard incoming batches over a device mesh: batch arrays are
        placed with this ``NamedSharding`` before entering the jitted step,
        so GSPMD compiles the step data-parallel and inserts the gradient
        all-reduce (psum over ICI) inside the ONE executable.  Pass None to
        go back to single-device placement.  (ParallelWrapper's integration
        point — the sharding is part of the model's own step compilation,
        not a wrapper-side patch.)"""
        self._batchSharding = sharding

    def _place_batch(self, arr):
        return _place_batch_with(self._batchSharding, arr)

    def _cast_compute(self, tree):
        """f32 leaves -> compute dtype (no-op at full precision)."""
        if self._computeDtype == jnp.float32:
            return tree
        cd = self._computeDtype
        return jax.tree.map(
            lambda a: a.astype(cd) if hasattr(a, "dtype")
            and a.dtype == jnp.float32 else a, tree)

    # ------------------------------------------------------------------
    # initialization
    # ------------------------------------------------------------------
    def init(self, params: Optional[Params] = None) -> "MultiLayerNetwork":
        """Build params/state/updater-state as ONE jitted computation.

        Eager per-tensor init would issue hundreds of tiny dispatches (very
        slow on a remote-compile TPU path); a single traced function compiles
        once and materializes everything device-side.
        """
        # Fail like the reference's config validation, not with a cryptic
        # shape error deep in the first matmul: every parameterized layer
        # must know nIn by now (explicitly or via setInputType inference).
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "nOut", None) and \
                    not getattr(layer, "nIn", True):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}): nIn not set and "
                    "not inferrable — set .nIn(...) on the layer or "
                    ".setInputType(...) on the configuration")

        def build_ps(root):
            p_tree: Params = {}
            s_tree: Dict[str, Dict[str, jax.Array]] = {}
            for i, layer in enumerate(self.conf.layers):
                it = self.conf.layerInputTypes[i]
                p = layer.initParams(jax.random.fold_in(root, i), it,
                                     self._dtype)
                if p:
                    p_tree[str(i)] = p
                if hasattr(layer, "initState"):
                    s_tree[str(i)] = layer.initState(it, self._dtype)
            return p_tree, s_tree

        if params is not None:
            self.params_ = params
            # jaxlint: disable=retrace-closure -- one-shot state init at build: traced once per init()
            self.state_ = jax.jit(lambda: {
                str(i): layer.initState(self.conf.layerInputTypes[i],
                                        self._dtype)
                for i, layer in enumerate(self.conf.layers)
                if hasattr(layer, "initState")})()
        else:
            # jaxlint: disable=retrace-closure -- one-shot param init at build: traced once per init()
            self.params_, self.state_ = jax.jit(build_ps)(
                jax.random.PRNGKey(self._rngSeed))
        self._initOptState()
        return self

    def _initOptState(self) -> None:
        def build_opt(p_tree):
            opt = {}
            for i, layer in enumerate(self.conf.layers):
                li = str(i)
                if li not in p_tree:
                    continue
                opt[li] = {path: self._updaterFor(layer, pname).init(pval)
                           for path, pname, pval
                           in _iter_leaf_params(p_tree[li])}
            return opt

        # jaxlint: disable=retrace-closure -- one-shot optimizer-state init: traced once per init()
        self.optState_ = jax.jit(build_opt)(self.params_)

    def _updaterFor(self, layer, pname: str):
        return _updater_for(self.conf.globalConf, layer, pname)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _forward(self, params: Params, state, x, train: bool, key, mask=None,
                 carries=None):
        """Run the stack.  ``mask`` is the (b, t) feature/timestep mask;
        ``carries`` maps RNN layer index -> initial carry (None = zeros,
        i.e. fresh sequences).  Returns (out, new_state, new_carries) — the
        reference's analogue of carries is the rnn ``stateMap`` used by
        ``rnnTimeStep``/TBPTT (``MultiLayerNetwork.rnnActivateUsingStoredState``).
        """
        miniBatch = x.shape[0]
        new_state = {}
        new_carries = {}
        for i, layer in enumerate(self.conf.layers):
            if i in self.conf.preProcessors:
                x = self.conf.preProcessors[i].preProcess(x, miniBatch)
            lkey = jax.random.fold_in(key, i) if key is not None else None
            st = state.get(str(i), {})
            p = params.get(str(i), {})
            if getattr(layer, "producesMask", False):
                # e.g. MaskingLayer: derives the timestep mask from the
                # data; downstream mask-aware layers see the new mask
                mask = layer.computeMask(x, mask)
            if getattr(layer, "isRNN", False):
                c0 = (carries or {}).get(str(i))
                if c0 is None:
                    c0 = layer.initialCarry(x.shape[0], x.dtype)
                x, cfin = layer.scanSeq(p, x, train, lkey, c0, mask)
                new_carries[str(i)] = cfin
                st2 = st
            elif getattr(layer, "acceptsMask", False):
                x, st2 = layer.forward(p, x, train, lkey, st, mask=mask)
            else:
                x, st2 = layer.forward(p, x, train, lkey, st)
            x = _constrain_act(x)
            if st2:
                new_state[str(i)] = st2
        return x, new_state, new_carries

    def _regScore(self, params: Params):
        return _reg_penalty((layer, params[str(i)])
                            for i, layer in enumerate(self.conf.layers)
                            if str(i) in params)

    def _auxLoss(self, new_state):
        """Sum of auxiliary losses layers emitted through their state
        (``hasAuxLoss`` layers — e.g. the MoE router's Switch
        load-balancing term, already scaled at the layer).  Added to the
        training loss so the router trains; differentiable because
        ``new_state`` is computed inside the traced loss."""
        total = 0.0
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "hasAuxLoss", False):
                st = new_state.get(str(i))
                if st and "auxLoss" in st:
                    total = total + st["auxLoss"]
        return total

    def _lossFn(self, params: Params, state, x, y, fmask, lmask, key,
                carries=None):
        # state stays f32: BatchNormalization accumulates its EMA in the
        # state dtype and casts only the normalization arithmetic (see
        # BatchNormalization.forward) — casting here would quantize masters
        out, new_state, new_carries = self._forward(
            self._cast_compute(params), state,
            self._cast_compute(x), True, key, fmask,
            self._cast_compute(carries))
        outLayer = self.conf.layers[-1]
        if not outLayer.hasLoss():
            raise ValueError("Last layer must be an output/loss layer to fit")
        if self._computeDtype != jnp.float32:
            out = out.astype(jnp.float32)   # loss in f32 under bf16 compute
        per_ex = outLayer.computeScore(y, out, lmask)
        data_loss = jnp.mean(per_ex)
        return (data_loss + self._regScore(params)
                + self._auxLoss(new_state),
                (new_state, new_carries, data_loss))

    # ------------------------------------------------------------------
    # the fused train step (single XLA executable)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _stepFn(self):
        """The RAW fused train step (fwd + loss + bwd + updater) —
        ``_trainStep`` jits it for single-device/DP-by-placement use,
        and ``parallel.meshtrainer.MeshTrainer`` compiles the SAME
        function with a ShardingPlan's explicit in/out shardings, so
        every mesh shape executes one stepping path."""
        layers = self.conf.layers

        def step(params, optState, state, x, y, fmask, lmask, key,
                 iteration, epoch, carries, lrScale):
            grad_fn = jax.value_and_grad(self._lossFn, has_aux=True)
            (loss, (new_state, new_carries, data_loss)), grads = grad_fn(
                params, state, x, y, fmask, lmask, key, carries)
            new_params, new_opt = _apply_updates(
                ((str(i), layer) for i, layer in enumerate(layers)),
                self.conf.globalConf, params, grads, optState, iteration,
                epoch, lrScale=lrScale)
            return new_params, new_opt, new_state, loss, new_carries

        return step

    @functools.cached_property
    def _trainStep(self):
        # with the persistent AOT cache configured, the fused step
        # dispatches through it (warm boots load the serialized
        # executable instead of re-tracing); plain jit otherwise
        from deeplearning4j_tpu.compile.aotcache import wrap_jit
        return wrap_jit(jax.jit(self._stepFn, donate_argnums=(0, 1, 2)),
                        kind="train_step", model=self)

    @functools.cached_property
    def _outputFn(self):
        def run(params, state, x, fmask, carries):
            out, _, new_carries = self._forward(
                self._cast_compute(params), state,
                self._cast_compute(x), False, None, fmask,
                self._cast_compute(carries))
            if self._computeDtype != jnp.float32:
                out = out.astype(jnp.float32)
            return out, new_carries
        return jax.jit(run)

    @functools.cached_property
    def _scoreFn(self):
        def run(params, state, x, y, fmask, lmask):
            out, _, _ = self._forward(
                self._cast_compute(params), state,
                self._cast_compute(x), False, None, fmask)
            if self._computeDtype != jnp.float32:
                out = out.astype(jnp.float32)
            per_ex = self.conf.layers[-1].computeScore(y, out, lmask)
            return jnp.mean(per_ex) + self._regScore(params)
        return jax.jit(run)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _ensure_trace_mesh(self) -> None:
        """Drop step executables compiled under a ParallelWrapper mesh
        when this net is used OUTSIDE any wrapper (the mesh routing —
        e.g. ring attention — is baked into the trace)."""
        from deeplearning4j_tpu.parallel.mesh import active_mesh
        if getattr(self, "_meshTrace", None) is not None \
                and active_mesh() is None:
            for k in ("_trainStep", "_outputFn", "_scoreFn"):
                self.__dict__.pop(k, None)
            self._meshTrace = None

    def fit(self, data, labels=None, epochs: int = 1) -> None:
        self._ensure_trace_mesh()
        if self.params_ is None:
            self.init()
        if isinstance(data, DataSet):
            self._fitBatch(data)
        elif isinstance(data, DataSetIterator):
            # streaming sources (file decode / CSV parse per record)
            # auto-engage the sharded producer pool + H2D staging ring;
            # in-memory iterators pass through unchanged.  hostShard
            # stays OFF here: a bare fit has no cross-host all-reduce,
            # so under jax.distributed each process must see the full
            # stream (ParallelWrapper/SharedTrainingMaster opt in)
            from deeplearning4j_tpu.datavec.pipeline import maybe_prefetch
            it = maybe_prefetch(data, hostShard=False)
            try:
                for _ in range(epochs):
                    self._fitEpoch(it)
            finally:
                if it is not data:
                    it.close()      # release the pool's shm slots
        elif labels is not None:
            self._fitBatch(DataSet(data, labels))
        else:
            raise TypeError(f"Cannot fit on {type(data)}")

    def _fitEpoch(self, it: DataSetIterator) -> None:
        notifyListeners(self._listeners, "onEpochStart", self)
        it.reset()
        while it.hasNext():
            self._fitBatch(etl_fetch(it))
        self.epochCount += 1
        notifyListeners(self._listeners, "onEpochEnd", self)

    def _fitBatch(self, ds: DataSet) -> None:
        from deeplearning4j_tpu.nn.conf import BackpropType
        with h2d_span():
            x = self._place_batch(ds.features.jax.astype(self._dtype))
            y = self._place_batch(ds.labels.jax)
            fmask = self._place_batch(
                ds.featuresMask.jax if ds.featuresMask is not None else None)
            lmask = self._place_batch(
                ds.labelsMask.jax if ds.labelsMask is not None else None)
        self.lastBatchSize = int(x.shape[0])
        self._lastInput = x      # device ref for StatsListener activations

        algo = str(self.conf.globalConf.get("optimizationAlgo")
                   or "STOCHASTIC_GRADIENT_DESCENT").upper()
        # TBPTT needs per-timestep (rank-3) labels; otherwise fall back to
        # standard BP (reference: doTruncatedBPTT label-rank requirement)
        with train_step_span(self, self.lastBatchSize):
            if algo != "STOCHASTIC_GRADIENT_DESCENT":
                # legacy line-search solvers (LBFGS/CG/line GD): one
                # line-searched iteration per fit call — reference Solver
                # semantics (optimize/solvers.py)
                self._runSolverStep(x, y, fmask, lmask, algo)
            elif (self.conf.backpropType == BackpropType.TruncatedBPTT
                    and x.ndim == 3 and y.ndim == 3
                    and x.shape[2] > self.conf.tbpttFwdLength):
                self._fitTbptt(x, y, fmask, lmask)
            else:
                self._runTrainStep(x, y, fmask, lmask, carries=None)
        self.iterationCount += 1
        if not in_microbatch():
            # OOM-retry halves share one logical iteration — the
            # supervisor fires iterationDone ONCE at the step boundary
            notifyListeners(self._listeners, "iterationDone", self,
                            self.iterationCount, self.epochCount)

    def _runSolverStep(self, x, y, fmask, lmask, algo: str) -> None:
        from jax.flatten_util import ravel_pytree

        from deeplearning4j_tpu.optimize.solvers import make_solver
        flat, unravel = ravel_pytree(self.params_)
        if getattr(self, "_solver", None) is None or \
                self._solverAlgo != algo or \
                self._solverSize != flat.size:
            self._solver = make_solver(
                algo, int(self.conf.globalConf.get(
                    "maxNumLineSearchIterations") or 5))
            self._solverAlgo, self._solverSize = algo, flat.size
            key = jax.random.fold_in(self._fitKey, 0)
            state = self.state_

            def loss_flat(v, xb, yb, fm, lm):
                loss, _aux = self._lossFn(unravel(v), state, xb, yb,
                                          fm, lm, key, None)
                return loss

            self._solver.bind(loss_flat)
        # masks enter as jit args too; None stays None (static)
        new_flat, f_new = self._solver.step(flat, x, y, fmask, lmask)
        self.params_ = unravel(new_flat)
        # jaxlint: sync-ok -- the line-search solver contract needs the host loss each iteration
        self._score = float(f_new)
        self._scoreArr = None

    def _runTrainStep(self, x, y, fmask, lmask, carries):
        self._fitKey, key = jax.random.split(self._fitKey)
        (self.params_, self.optState_, new_state, loss,
         new_carries) = self._trainStep(
            self.params_, self.optState_, self.state_, x, y, fmask, lmask,
            key, jnp.asarray(self.iterationCount),
            jnp.asarray(self.epochCount), carries,
            jnp.asarray(self._lrScale, jnp.float32))
        if new_state:
            # jaxlint: disable=donation-use-after -- update() replaces
            # every donated leaf with the freshly returned new_state
            # values; no stale buffer survives the in-place refresh
            self.state_.update(new_state)
        # Keep the loss as an async device scalar: syncing it here would
        # serialize every step on a host round-trip.  score()
        # materializes it lazily on demand.
        self._scoreArr = loss
        if panic_enabled():
            # NAN_PANIC/INF_PANIC (reference: profilingConfigurableHookOut)
            # — opt-in mode that needs the value immediately.
            # jaxlint: sync-ok -- panic mode opts INTO a per-step sync to fail on the exact step
            self._score = float(loss)
            self._scoreArr = None
            check_panic(self._score)
        return new_carries

    def _fitTbptt(self, x, y, fmask, lmask) -> None:
        """Truncated BPTT: chunk the time axis, carry RNN state (detached)
        across chunks.  Reference: ``MultiLayerNetwork.doTruncatedBPTT`` +
        ``rnnActivateUsingStoredState``."""
        t = x.shape[2]
        L = self.conf.tbpttFwdLength
        # explicit zero carries for chunk 0: keeps the carry pytree structure
        # identical across chunks, so the train step traces/compiles ONCE
        carries = self._zeroCarries(x.shape[0])
        for start in range(0, t, L):
            end = min(start + L, t)
            xc = x[:, :, start:end]
            yc = y[:, :, start:end] if y.ndim == 3 else y
            fc = fmask[:, start:end] if fmask is not None else None
            lc = lmask[:, start:end] if lmask is not None else None
            # carries come back as concrete arrays -> implicitly detached
            # (the reference equally truncates gradients at chunk edges)
            carries = self._runTrainStep(xc, yc, fc, lc, carries)

    def _zeroCarries(self, batch: int):
        """Fresh-sequence RNN carries for every recurrent layer (concrete
        zeros — cheap; keeps jit pytree structure stable vs passing None)."""
        out = {}
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "isRNN", False):
                out[str(i)] = layer.initialCarry(batch, self._dtype)
        return out or None

    def output(self, x, train: bool = False, featuresMask=None) -> NDArray:
        self._ensure_trace_mesh()
        xv = x.jax if isinstance(x, NDArray) else jnp.asarray(x)
        fm = None
        if featuresMask is not None:
            fm = featuresMask.jax if isinstance(featuresMask, NDArray) \
                else jnp.asarray(featuresMask)
        out, _ = self._outputFn(self.params_, self.state_,
                                xv.astype(self._dtype), fm, None)
        return NDArray(out)

    # ------------------------------------------------------------------
    # stateful RNN inference (reference: MultiLayerNetwork.rnnTimeStep /
    # rnnClearPreviousState / rnnGetPreviousState — the ``stateMap``)
    # ------------------------------------------------------------------
    def rnnTimeStep(self, x) -> NDArray:
        """Feed one or more timesteps, carrying hidden state across calls.

        2d input (b, nIn) = single step -> (b, nOut); 3d (b, nIn, t) ->
        (b, nOut, t).  State persists until ``rnnClearPreviousState``.
        """
        for layer in self.conf.layers:
            if type(layer).__name__ == "Bidirectional":
                # streaming one step at a time cannot see the future the
                # backward half needs (the reference throws here too)
                raise ValueError(
                    "rnnTimeStep is not supported for bidirectional networks")
        xv = x.jax if isinstance(x, NDArray) else jnp.asarray(x)
        single = xv.ndim == 2
        if single:
            xv = xv[:, :, None]
        if self._rnnCarries is None:
            self._rnnCarries = self._zeroCarries(int(xv.shape[0]))
        out, self._rnnCarries = self._outputFn(
            self.params_, self.state_, xv.astype(self._dtype), None,
            self._rnnCarries)
        return NDArray(out[:, :, -1] if single and out.ndim == 3 else out)

    def rnnClearPreviousState(self) -> None:
        self._rnnCarries = None

    def rnnGetPreviousState(self, layerIdx: int):
        if self._rnnCarries is None:
            return None
        return self._rnnCarries.get(str(layerIdx))

    def rnnSetPreviousState(self, layerIdx: int, state) -> None:
        if self._rnnCarries is None:
            self._rnnCarries = {}
        self._rnnCarries[str(layerIdx)] = state

    def feedForward(self, x) -> List[NDArray]:
        """All layer activations (inference mode)."""
        xv = x.jax if isinstance(x, NDArray) else jnp.asarray(x)
        acts = [NDArray(xv)]
        cur = xv.astype(self._dtype)
        for i, layer in enumerate(self.conf.layers):
            if i in self.conf.preProcessors:
                cur = self.conf.preProcessors[i].preProcess(cur, cur.shape[0])
            cur, _ = layer.forward(self.params_.get(str(i), {}), cur, False,
                                   None, self.state_.get(str(i), {}))
            acts.append(NDArray(cur))
        return acts

    def predict(self, x) -> np.ndarray:
        out = self.output(x).jax
        # FF output is (b, nOut): argmax over -1.  RNN output is (b, nOut, t)
        # (DL4J layout): the class axis is 1, NOT the trailing time axis.
        axis = 1 if out.ndim == 3 else -1
        # jaxlint: sync-ok -- predict() returns host labels by contract (API boundary)
        return np.asarray(jnp.argmax(out, axis=axis))

    def pretrain(self, iterator, epochs: int = 1) -> None:
        """Layerwise unsupervised pretraining (reference:
        ``MultiLayerNetwork.pretrain(DataSetIterator)``): every layer
        with ``isPretrainLayer`` (VariationalAutoencoder) trains its own
        ``pretrainLoss`` on the activations feeding it, one fused jitted
        step per layer (fwd-to-layer + ELBO + bwd + updater)."""
        from deeplearning4j_tpu.learning.config import Sgd
        if self.params_ is None:
            self.init()
        updater = self.conf.globalConf.get("updater") or Sgd(1e-2)
        for li, layer in enumerate(self.conf.layers):
            if not getattr(layer, "isPretrainLayer", False):
                continue
            key = str(li)
            params = self.params_[key]
            opt = {n: updater.init(v) for n, v in params.items()}

            def step(params, opt, x, it, skey, _li=li, _layer=layer):
                def loss_fn(p):
                    h = x
                    for j in range(_li):     # frozen upstream, inference
                        jl = self.conf.layers[j]
                        if j in self.conf.preProcessors:
                            h = self.conf.preProcessors[j].preProcess(
                                h, h.shape[0])
                        h, _ = jl.forward(self.params_[str(j)], h, False,
                                          None, self.state_.get(str(j),
                                                                {}))
                    if _li in self.conf.preProcessors:
                        h = self.conf.preProcessors[_li].preProcess(
                            h, h.shape[0])
                    return _layer.pretrainLoss(p, h, skey)
                loss, g = jax.value_and_grad(loss_fn)(params)
                newp, newo = {}, {}
                lr = updater.currentLr(it, 0)
                for n, gv in g.items():
                    upd, st = updater.apply(gv, opt[n], lr, it,
                                            param=params[n])
                    newp[n] = params[n] - upd
                    newo[n] = st
                return newp, newo, loss
            # jaxlint: disable=retrace-loop -- one executable per pretrained LAYER by design
            # (the layer is baked into the trace); reused across every epoch of that layer
            jstep = jax.jit(step)

            it_count = 0
            loss = None
            # jaxlint: disable=host-sync -- epochs is a Python int argument
            for _ in range(int(epochs)):
                if hasattr(iterator, "reset"):
                    iterator.reset()
                for ds in iterator:
                    x = ds.features.jax.astype(self._dtype)
                    params, opt, loss = jstep(
                        params, opt, x, jnp.asarray(it_count, jnp.int32),
                        jax.random.fold_in(self._fitKey, it_count))
                    it_count += 1
            self.params_[key] = params
            if loss is not None:
                self._scoreArr = loss

    def score(self, ds: Optional[DataSet] = None) -> float:
        if ds is None:
            if self._scoreArr is not None:
                # jaxlint: sync-ok -- score() IS the lazy materialization point of the async loss
                self._score = float(self._scoreArr)
                self._scoreArr = None
            return self._score
        self._ensure_trace_mesh()
        fmask = ds.featuresMask.jax if ds.featuresMask is not None else None
        lmask = ds.labelsMask.jax if ds.labelsMask is not None else None
        return float(self._scoreFn(self.params_, self.state_,
                                   ds.features.jax.astype(self._dtype),
                                   ds.labels.jax, fmask, lmask))

    def evaluate(self, it: DataSetIterator, metric: str = "classification"):
        ev = {"classification": Evaluation, "regression": RegressionEvaluation,
              "roc": ROC}[metric]()
        it.reset()
        while it.hasNext():
            # etl_fetch also CONSUMES async-prefetch waits noted in
            # hasNext — a bare it.next() here would leave them pending to
            # poison the next training fetch's stall accounting
            ds = etl_fetch(it)
            out = self.output(ds.features, featuresMask=ds.featuresMask)
            # jaxlint: sync-ok -- evaluation is host-side by contract (metrics math in numpy)
            ev.eval(ds.labels.numpy(), out.numpy(),
                    # jaxlint: disable=host-sync -- same evaluation D2H as the line above
                    ds.labelsMask.numpy() if ds.labelsMask is not None else None)
        it.reset()
        return ev

    def evaluateROC(self, it: DataSetIterator) -> ROC:
        return self.evaluate(it, metric="roc")

    def evaluateRegression(self, it: DataSetIterator) -> RegressionEvaluation:
        return self.evaluate(it, metric="regression")

    # -- listeners -------------------------------------------------------
    def setListeners(self, *listeners) -> None:
        if len(listeners) == 1 and isinstance(listeners[0], (list, tuple)):
            listeners = tuple(listeners[0])
        self._listeners = list(listeners)

    def addListeners(self, *listeners) -> None:
        self._listeners.extend(listeners)

    def getListeners(self) -> List:
        return self._listeners

    def removeListener(self, listener) -> None:
        if listener in self._listeners:
            self._listeners.remove(listener)

    # -- params ----------------------------------------------------------
    def params(self) -> NDArray:
        """Single flattened param vector (reference: ``paramsFlattened``),
        assembled as a DEVICE-RESIDENT view: one ``jnp.concatenate`` over
        the ravelled leaves, no host round-trip.  Callers that need host
        bytes (serialization) take them explicitly via ``.numpy()``."""
        chunks = []
        for i in range(len(self.conf.layers)):
            li = str(i)
            if li in self.params_:
                for _path, _pname, v in _iter_leaf_params(self.params_[li]):
                    chunks.append(_ravel_replicated(v))
        if not chunks:
            return NDArray(jnp.zeros((0,)))
        return NDArray(jnp.concatenate(chunks))

    def setParams(self, flat) -> None:
        """Write a flat vector back into the param tree — device-side
        slicing (the H2D transfer, if any, happens once for the whole
        vector; nothing is pulled back to the host)."""
        vec = jnp.ravel(flat.jax if isinstance(flat, NDArray)
                        else jnp.asarray(flat))
        pos = 0
        for i in range(len(self.conf.layers)):
            li = str(i)
            if li in self.params_:
                for path, _pname, cur in _iter_leaf_params(self.params_[li]):
                    n = int(np.prod(cur.shape))
                    _set_leaf(self.params_[li], path,
                              vec[pos:pos + n].reshape(cur.shape)
                              .astype(cur.dtype))
                    pos += n
        if pos != vec.size:
            raise ValueError(f"Param vector length {vec.size} != model {pos}")

    def numParams(self) -> int:
        if self.params_ is None:
            return 0
        return int(sum(int(np.prod(v.shape))
                       for v in jax.tree_util.tree_leaves(self.params_)))

    def paramTable(self) -> Dict[str, NDArray]:
        out = {}
        for li, lp in self.params_.items():
            for path, _pname, v in _iter_leaf_params(lp):
                out[f"{li}_{path}"] = NDArray(v)
        return out

    def getParam(self, key: str) -> NDArray:
        li, path = key.split("_", 1)
        return NDArray(_get_leaf(self.params_[li], path))

    def setParam(self, key: str, value) -> None:
        li, path = key.split("_", 1)
        v = value.jax if isinstance(value, NDArray) else jnp.asarray(value)
        cur = _get_leaf(self.params_[li], path)
        _set_leaf(self.params_[li], path, v.astype(cur.dtype))

    # -- bookkeeping ----------------------------------------------------
    def getEpochCount(self) -> int:
        return self.epochCount

    def getIterationCount(self) -> int:
        return self.iterationCount

    def getLayerWiseConfigurations(self) -> MultiLayerConfiguration:
        return self.conf

    def getnLayers(self) -> int:
        return len(self.conf.layers)

    def clone(self) -> "MultiLayerNetwork":
        import copy
        net = MultiLayerNetwork(self.conf)
        net.params_ = jax.tree_util.tree_map(lambda v: v, self.params_)
        net.state_ = jax.tree_util.tree_map(lambda v: v, self.state_)
        net._initOptState()
        net.optState_ = copy.deepcopy(
            jax.tree_util.tree_map(lambda v: v, self.optState_))
        return net

    def summary(self) -> str:
        lines = [f"{'idx':<4} {'layer':<28} {'params':>10} {'in -> out'}"]
        total = 0
        for i, layer in enumerate(self.conf.layers):
            li = str(i)
            n = sum(int(np.prod(v.shape)) for _p, _k, v in
                    _iter_leaf_params(self.params_.get(li, {}))) \
                if self.params_ else 0
            total += n
            it = self.conf.layerInputTypes[i]
            ot = layer.getOutputType(it) if it else None
            lines.append(f"{i:<4} {type(layer).__name__:<28} {n:>10} "
                         f"{it.getShape() if it else '?'} -> "
                         f"{ot.getShape() if ot else '?'}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)
