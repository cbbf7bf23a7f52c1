"""ComputationGraph — DAG model with multi-input/multi-output training.

Reference: deeplearning4j-nn ``org/deeplearning4j/nn/graph/
ComputationGraph.java`` (topologicalSortOrder, GraphVertex.doForward/
doBackward — SURVEY.md §3.2).

Same TPU-first design as MultiLayerNetwork: the whole DAG (forward over topo
order + all losses + backward + updaters) compiles into ONE fused XLA
executable; vertices are pure functions so ``jax.grad`` handles the
reference's per-vertex ``doBackward`` epsilon bookkeeping.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.learning.config import Sgd
from deeplearning4j_tpu.learning.regularization import WeightDecay
from deeplearning4j_tpu.models.multilayer import (_apply_updates,
                                                  _constrain_act, _get_leaf,
                                                  _grad_normalize,
                                                  _iter_leaf_params,
                                                  _param_key_order,
                                                  _place_batch_with,
                                                  _ravel_replicated,
                                                  _reg_penalty, _set_leaf,
                                                  _updater_for)
from deeplearning4j_tpu.models.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.layers import Layer
from deeplearning4j_tpu.ops import NDArray
from deeplearning4j_tpu.optimize.listeners import notifyListeners
from deeplearning4j_tpu.profiler import check_panic, panic_enabled
from deeplearning4j_tpu.telemetry import (etl_fetch, h2d_span,
                                          in_microbatch, train_step_span)


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params_: Optional[Dict] = None
        self.state_: Dict[str, Dict] = {}
        self.optState_: Optional[Dict] = None
        self.iterationCount = 0
        self.epochCount = 0
        self.lastBatchSize = 0
        self._score = 0.0
        self._scoreArr = None  # pending async device-scalar loss
        self._listeners: List = []
        self._rngSeed = int(conf.globalConf.get("seed", 123) or 123)
        self._dtype = jnp.float32
        dt = str(conf.globalConf.get("dataType") or "FLOAT").upper()
        self._computeDtype = jnp.bfloat16 \
            if dt in ("BFLOAT16", "HALF", "FLOAT16") else jnp.float32
        self._fitKey = jax.random.PRNGKey(self._rngSeed ^ 0x6EED)
        self._batchSharding = None  # set by ParallelWrapper (DP over mesh)
        self._lrScale = 1.0  # FaultTolerantTrainer's divergence backoff
        self._lossNodes = [n for n in conf.outputs
                           if isinstance(conf.nodes[n][0], Layer)
                           and conf.nodes[n][0].hasLoss()]

    def setLrScale(self, scale: float) -> None:
        """See MultiLayerNetwork.setLrScale — the fault supervisor's
        rollback backoff; traced data, changing it never retraces."""
        # jaxlint: disable=host-sync -- scale is a host config scalar from the supervisor
        self._lrScale = float(scale)

    def getLrScale(self) -> float:
        return self._lrScale

    # ------------------------------------------------------------------
    def init(self, params: Optional[Dict] = None) -> "ComputationGraph":
        """Single jitted init (see MultiLayerNetwork.init rationale)."""
        def build_ps(root):
            p_tree: Dict[str, Dict] = {}
            s_tree: Dict[str, Dict] = {}
            for idx, name in enumerate(self.conf.topoOrder):
                node, _ = self.conf.nodes[name]
                if isinstance(node, Layer):
                    it = self.conf.vertexInputTypes.get(name)
                    p = node.initParams(jax.random.fold_in(root, idx), it,
                                        self._dtype)
                    if p:
                        p_tree[name] = p
                if hasattr(node, "initState"):
                    s_tree[name] = node.initState(
                        self.conf.vertexInputTypes.get(name), self._dtype)
            return p_tree, s_tree

        if params is not None:
            self.params_ = params
            # jaxlint: disable=retrace-closure -- one-shot state init at build: traced once per init()
            self.state_ = jax.jit(lambda: {
                name: self.conf.nodes[name][0].initState(
                    self.conf.vertexInputTypes.get(name), self._dtype)
                for name in self.conf.topoOrder
                if hasattr(self.conf.nodes[name][0], "initState")})()
        else:
            # jaxlint: disable=retrace-closure -- one-shot param init at build: traced once per init()
            self.params_, self.state_ = jax.jit(build_ps)(
                jax.random.PRNGKey(self._rngSeed))
        self._initOptState()
        return self

    def _initOptState(self) -> None:
        def build_opt(p_tree):
            # keyed by leaf PATH so nested layers (Bidirectional) work
            return {name: {path: self._updaterFor(
                        self.conf.nodes[name][0], pname).init(pval)
                           for path, pname, pval in _iter_leaf_params(lp)}
                    for name, lp in p_tree.items()}

        # jaxlint: disable=retrace-closure -- one-shot optimizer-state init: traced once per init()
        self.optState_ = jax.jit(build_opt)(self.params_ or {})

    def _updaterFor(self, layer, pname: str):
        return _updater_for(self.conf.globalConf, layer, pname)

    # ------------------------------------------------------------------
    def _forward(self, params, state, inputs: Sequence, train: bool, key,
                 mask=None, carries=None):
        """Forward over the cached topological order (reference:
        ``topologicalSortOrder()`` + per-vertex ``doForward``).

        ``mask`` is a tuple of per-INPUT (b, t) feature/timestep masks
        aligned with ``conf.inputs`` (or None) and flows through the DAG
        like the reference's ``feedForwardMaskArrays``: each vertex sees
        its first masked input's mask, and the mask dies wherever the
        (statically known) output format leaves RNN.  ``carries`` maps RNN
        vertex name -> initial carry (None = zeros, fresh sequences) — the
        reference CG's rnn ``stateMap`` (``ComputationGraph.rnnTimeStep`` /
        ``rnnActivateUsingStoredState``)."""
        acts: Dict[str, Any] = {}
        miniBatch = inputs[0].shape[0]
        mmap: Dict[str, Any] = {}
        for i, name in enumerate(self.conf.inputs):
            acts[name] = inputs[i]
            if mask is not None and i < len(mask):
                mmap[name] = mask[i]
        out_types = self.conf.vertexOutputTypes
        new_state: Dict[str, Dict] = {}
        new_carries: Dict[str, Any] = {}
        for idx, name in enumerate(self.conf.topoOrder):
            node, ins = self.conf.nodes[name]
            xs = [acts[i] for i in ins]
            m = next((mmap[i] for i in ins if mmap.get(i) is not None), None)
            if isinstance(node, Layer):
                x = xs[0]
                if name in self.conf.preProcessors:
                    x = self.conf.preProcessors[name].preProcess(x, miniBatch)
                if getattr(node, "producesMask", False):
                    # e.g. MaskingLayer: derive the timestep mask from the
                    # data; downstream vertices see the new mask
                    m = node.computeMask(x, m)
                    mmap[name] = m
                lkey = jax.random.fold_in(key, idx) if key is not None else None
                if getattr(node, "isRNN", False):
                    c0 = (carries or {}).get(name)
                    if c0 is None:
                        c0 = node.initialCarry(x.shape[0], x.dtype)
                    y, cfin = node.scanSeq(params.get(name, {}), x, train,
                                           lkey, c0, m)
                    new_carries[name] = cfin
                    st2 = {}
                elif getattr(node, "acceptsMask", False):
                    y, st2 = node.forward(params.get(name, {}), x, train,
                                          lkey, state.get(name, {}),
                                          mask=m)
                else:
                    y, st2 = node.forward(params.get(name, {}), x, train,
                                          lkey, state.get(name, {}))
                if st2:
                    new_state[name] = st2
                acts[name] = _constrain_act(y)
            else:
                acts[name] = _constrain_act(node.forward(*xs))
            ot = out_types.get(name)
            if m is not None and (ot is None or ot.kind == "RNN"):
                mmap[name] = m
        return acts, new_state, new_carries

    def _sumLosses(self, acts, labels, masks):
        """Accumulate every output layer's loss — THE loss semantics, shared
        by training (_lossFn) and reporting (score)."""
        total = 0.0
        for i, name in enumerate(self.conf.outputs):
            node = self.conf.nodes[name][0]
            if isinstance(node, Layer) and node.hasLoss():
                mask = masks[i] if masks is not None else None
                total = total + jnp.mean(node.computeScore(labels[i],
                                                           acts[name], mask))
        return total

    def _cast_compute(self, tree):
        """f32 -> compute dtype (mixed precision; see MultiLayerNetwork)."""
        if self._computeDtype == jnp.float32:
            return tree
        cd = self._computeDtype
        return jax.tree.map(
            lambda a: a.astype(cd) if hasattr(a, "dtype")
            and a.dtype == jnp.float32 else a, tree)

    def _lossFn(self, params, state, inputs, labels, masks, key,
                fmask=None, carries=None):
        # state stays f32 (see MultiLayerNetwork._lossFn note)
        acts, new_state, new_carries = self._forward(
            self._cast_compute(params), state,
            self._cast_compute(inputs), True, key, fmask,
            self._cast_compute(carries))
        if self._computeDtype != jnp.float32:   # losses evaluate in f32
            acts = {n: (a.astype(jnp.float32) if hasattr(a, "astype") else a)
                    for n, a in acts.items()}
        total = self._sumLosses(acts, labels, masks)
        reg = _reg_penalty((self.conf.nodes[name][0], lp)
                           for name, lp in params.items())
        # layer-state aux channel (MoE Switch load balancing) — same
        # contract as MultiLayerNetwork._auxLoss, or a graph-hosted MoE
        # router would silently collapse onto one expert
        aux = 0.0
        for name in self.conf.topoOrder:
            if getattr(self.conf.nodes[name][0], "hasAuxLoss", False):
                st = new_state.get(name)
                if st and "auxLoss" in st:
                    aux = aux + st["auxLoss"]
        return total + reg + aux, (new_state, total, new_carries)

    def _runSolverStep(self, inputs, labels, masks, fmask,
                       algo: str) -> None:
        """Legacy line-search solvers for graph models (see
        MultiLayerNetwork._runSolverStep / optimize/solvers.py)."""
        from jax.flatten_util import ravel_pytree

        from deeplearning4j_tpu.optimize.solvers import make_solver
        flat, unravel = ravel_pytree(self.params_)
        if getattr(self, "_solver", None) is None or \
                self._solverAlgo != algo or self._solverSize != flat.size:
            self._solver = make_solver(
                algo, int(self.conf.globalConf.get(
                    "maxNumLineSearchIterations") or 5))
            self._solverAlgo, self._solverSize = algo, flat.size
            key = jax.random.fold_in(self._fitKey, 0)
            state = self.state_

            def loss_flat(v, ins, labs, mks, fm):
                loss, _aux = self._lossFn(unravel(v), state, ins, labs,
                                          mks, key, fm)
                return loss

            self._solver.bind(loss_flat)
        new_flat, f_new = self._solver.step(flat, inputs, labels, masks,
                                            fmask)
        self.params_ = unravel(new_flat)
        # jaxlint: sync-ok -- the line-search solver contract needs the host loss each iteration
        self._score = float(f_new)
        self._scoreArr = None

    @functools.cached_property
    def _stepFn(self):
        """Raw fused train step (see MultiLayerNetwork._stepFn): jitted
        plain by ``_trainStep``, or with a ShardingPlan's in/out
        shardings by ``parallel.meshtrainer.MeshTrainer`` — one stepping
        path for every mesh shape."""
        def step(params, optState, state, inputs, labels, masks, key,
                 iteration, epoch, fmask, carries, lrScale):
            grad_fn = jax.value_and_grad(self._lossFn, has_aux=True)
            (loss, (new_state, data_loss, new_carries)), grads = grad_fn(
                params, state, inputs, labels, masks, key, fmask, carries)
            new_params, new_opt = _apply_updates(
                ((name, self.conf.nodes[name][0]) for name in params),
                self.conf.globalConf, params, grads, optState, iteration,
                epoch, lrScale=lrScale)
            return new_params, new_opt, new_state, loss, new_carries

        return step

    @functools.cached_property
    def _trainStep(self):
        # persistent AOT cache dispatch when configured (see
        # MultiLayerNetwork._trainStep); plain jit otherwise
        from deeplearning4j_tpu.compile.aotcache import wrap_jit
        return wrap_jit(jax.jit(self._stepFn, donate_argnums=(0, 1, 2)),
                        kind="train_step", model=self)

    @functools.cached_property
    def _outputFn(self):
        def run(params, state, inputs, fmask, carries):
            acts, _, new_carries = self._forward(
                self._cast_compute(params), state,
                self._cast_compute(inputs), False, None, fmask,
                self._cast_compute(carries))
            outs = tuple(acts[n] for n in self.conf.outputs)
            if self._computeDtype != jnp.float32:
                outs = tuple(o.astype(jnp.float32) for o in outs)
            return outs, new_carries
        return jax.jit(run)

    # ------------------------------------------------------------------
    def _ensure_trace_mesh(self) -> None:
        """Drop executables compiled under a MeshTrainer plan when this
        graph is used OUTSIDE any mesh (see MultiLayerNetwork's
        _ensure_trace_mesh — the sharding constraints are baked into the
        trace)."""
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
        if isinstance(data, (DataSet, MultiDataSet)):
            self._fitBatch(data)
        elif isinstance(data, DataSetIterator):
            for _ in range(epochs):
                notifyListeners(self._listeners, "onEpochStart", self)
                data.reset()
                while data.hasNext():
                    self._fitBatch(etl_fetch(data))
                self.epochCount += 1
                notifyListeners(self._listeners, "onEpochEnd", self)
        elif labels is not None:
            self._fitBatch(DataSet(data, labels))
        else:
            raise TypeError(f"Cannot fit on {type(data)}")

    def setBatchSharding(self, sharding) -> None:
        """See MultiLayerNetwork.setBatchSharding — DP via GSPMD on the
        model's own compiled step (ParallelWrapper integration point)."""
        self._batchSharding = sharding

    def _place_batch(self, arr):
        return _place_batch_with(self._batchSharding, arr)

    def _fitBatch(self, ds) -> None:
        pb = self._place_batch
        fmask = None
        with h2d_span():
            if isinstance(ds, MultiDataSet):
                inputs = tuple(pb(f.jax.astype(self._dtype))
                               for f in ds.features)
                labels = tuple(pb(l.jax) for l in ds.labels)
                masks = tuple(pb(m.jax) for m in ds.labelsMasks) \
                    if ds.labelsMasks else None
                if getattr(ds, "featuresMasks", None):
                    fmask = tuple(pb(m.jax) if m is not None else None
                                  for m in ds.featuresMasks)
            else:
                inputs = (pb(ds.features.jax.astype(self._dtype)),)
                labels = (pb(ds.labels.jax),)
                masks = (pb(ds.labelsMask.jax),) \
                    if ds.labelsMask is not None else None
                if ds.featuresMask is not None:
                    fmask = (pb(ds.featuresMask.jax),)
        self.lastBatchSize = int(inputs[0].shape[0])
        algo = str(self.conf.globalConf.get("optimizationAlgo")
                   or "STOCHASTIC_GRADIENT_DESCENT").upper()
        if algo != "STOCHASTIC_GRADIENT_DESCENT":
            with train_step_span(self, self.lastBatchSize):
                self._runSolverStep(inputs, labels, masks, fmask, algo)
            self.iterationCount += 1
            if not in_microbatch():
                notifyListeners(self._listeners, "iterationDone", self,
                                self.iterationCount, self.epochCount)
            return
        from deeplearning4j_tpu.nn.conf import BackpropType
        # TBPTT needs per-timestep (rank-3) labels on every output
        # (reference: ComputationGraph.doTruncatedBPTT)
        with train_step_span(self, self.lastBatchSize):
            if self.conf.backpropType == BackpropType.TruncatedBPTT \
                    and all(i.ndim == 3 for i in inputs) \
                    and all(l.ndim == 3 for l in labels) \
                    and inputs[0].shape[2] > self.conf.tbpttFwdLength:
                self._fitTbptt(inputs, labels, masks, fmask)
            else:
                self._runTrainStep(inputs, labels, masks, fmask,
                                   carries=None)
        self.iterationCount += 1
        if not in_microbatch():
            # OOM-retry halves share one logical iteration — the
            # supervisor fires iterationDone ONCE at the step boundary
            notifyListeners(self._listeners, "iterationDone", self,
                            self.iterationCount, self.epochCount)

    def _runTrainStep(self, inputs, labels, masks, fmask, carries):
        self._fitKey, key = jax.random.split(self._fitKey)
        (self.params_, self.optState_, new_state, loss,
         new_carries) = self._trainStep(
            self.params_, self.optState_, self.state_, inputs, labels, masks,
            key, jnp.asarray(self.iterationCount),
            jnp.asarray(self.epochCount), fmask, carries,
            jnp.asarray(self._lrScale, jnp.float32))
        if new_state:
            # jaxlint: disable=donation-use-after -- update() replaces
            # every donated leaf with the freshly returned new_state
            # values; no stale buffer survives the in-place refresh
            self.state_.update(new_state)
        # Async device scalar; score() materializes lazily (see multilayer).
        self._scoreArr = loss
        if panic_enabled():
            # NAN_PANIC/INF_PANIC (reference: profilingConfigurableHookOut)
            # jaxlint: sync-ok -- panic mode opts INTO a per-step sync to fail on the exact step
            self._score = float(loss)
            self._scoreArr = None
            check_panic(self._score)
        return new_carries

    def _fitTbptt(self, inputs, labels, masks, fmask) -> None:
        """Truncated BPTT over the DAG: chunk the time axis, carry RNN
        vertex state (detached) across chunks.  Reference:
        ``ComputationGraph.doTruncatedBPTT`` +
        ``rnnActivateUsingStoredState``."""
        t = inputs[0].shape[2]
        L = self.conf.tbpttFwdLength
        carries = self._zeroCarries(int(inputs[0].shape[0]))
        for start in range(0, t, L):
            end = min(start + L, t)
            ic = tuple(x[:, :, start:end] for x in inputs)
            lc = tuple(y[:, :, start:end] if y.ndim == 3 else y
                       for y in labels)
            mc = tuple(m[:, start:end] for m in masks) \
                if masks is not None else None
            fc = tuple(m[:, start:end] if m is not None else None
                       for m in fmask) if fmask is not None else None
            carries = self._runTrainStep(ic, lc, mc, fc, carries)

    def _zeroCarries(self, batch: int):
        """Fresh-sequence carries for every recurrent vertex (concrete
        zeros keep the jit pytree structure stable vs passing None)."""
        out = {}
        for name in self.conf.topoOrder:
            node = self.conf.nodes[name][0]
            if getattr(node, "isRNN", False):
                out[name] = node.initialCarry(batch, self._dtype)
        return out or None

    def output(self, *inputs, featuresMask=None):
        self._ensure_trace_mesh()
        xs = tuple((x.jax if isinstance(x, NDArray) else jnp.asarray(x))
                   .astype(self._dtype) for x in inputs)
        fm = None
        if featuresMask is not None:
            if not isinstance(featuresMask, (tuple, list)):
                featuresMask = (featuresMask,)
            fm = tuple(
                (m.jax if isinstance(m, NDArray) else jnp.asarray(m))
                if m is not None else None for m in featuresMask)
        outs, _ = self._outputFn(self.params_, self.state_, xs, fm, None)
        res = [NDArray(o) for o in outs]
        return res[0] if len(res) == 1 else res

    def outputSingle(self, *inputs) -> NDArray:
        out = self.output(*inputs)
        return out[0] if isinstance(out, list) else out

    # ------------------------------------------------------------------
    # stateful RNN inference (reference: ComputationGraph.rnnTimeStep /
    # rnnClearPreviousState / rnnGetPreviousState — the vertex stateMap)
    # ------------------------------------------------------------------
    _rnnCarries = None

    def rnnTimeStep(self, *inputs):
        """Feed one or more timesteps, carrying RNN vertex state across
        calls.  2d inputs (b, nIn) = single step -> (b, nOut); 3d
        (b, nIn, t) -> (b, nOut, t)."""
        for name in self.conf.topoOrder:
            node = self.conf.nodes[name][0]
            if type(node).__name__ == "Bidirectional":
                # streaming one step at a time cannot see the future the
                # backward half needs (the reference throws here too)
                raise ValueError("rnnTimeStep is not supported for "
                                 "bidirectional networks")
        xs = []
        single = False
        for x in inputs:
            xv = x.jax if isinstance(x, NDArray) else jnp.asarray(x)
            if xv.ndim == 2:
                single = True
                xv = xv[:, :, None]
            xs.append(xv.astype(self._dtype))
        if self._rnnCarries is None:
            self._rnnCarries = self._zeroCarries(int(xs[0].shape[0]))
        outs, self._rnnCarries = self._outputFn(
            self.params_, self.state_, tuple(xs), None, self._rnnCarries)
        res = [NDArray(o[:, :, -1] if single and o.ndim == 3 else o)
               for o in outs]
        return res[0] if len(res) == 1 else res

    def rnnClearPreviousState(self) -> None:
        self._rnnCarries = None

    def rnnGetPreviousState(self, vertexName: str):
        if self._rnnCarries is None:
            return None
        return self._rnnCarries.get(vertexName)

    def rnnSetPreviousState(self, vertexName: str, state) -> None:
        if self._rnnCarries is None:
            self._rnnCarries = {}
        self._rnnCarries[vertexName] = state

    @functools.cached_property
    def _scoreFn(self):
        def run(params, state, inputs, labels, masks, fmask):
            acts, _, _ = self._forward(
                self._cast_compute(params), state,
                self._cast_compute(inputs), False, None, fmask)
            if self._computeDtype != jnp.float32:
                acts = {n: (a.astype(jnp.float32)
                            if hasattr(a, "astype") else a)
                        for n, a in acts.items()}
            return self._sumLosses(acts, labels, masks) + _reg_penalty(
                (self.conf.nodes[n][0], lp) for n, lp in params.items())
        return jax.jit(run)

    def score(self, ds=None) -> float:
        """With a DataSet: compute the loss on it (reference:
        ``ComputationGraph.score(DataSet)``); without: last training score."""
        if ds is None:
            if self._scoreArr is not None:
                # jaxlint: sync-ok -- score() IS the lazy materialization point of the async loss
                self._score = float(self._scoreArr)
                self._scoreArr = None
            return self._score
        fmask = None
        if isinstance(ds, MultiDataSet):
            inputs = tuple(f.jax.astype(self._dtype) for f in ds.features)
            labels = tuple(l.jax for l in ds.labels)
            masks = tuple(m.jax for m in ds.labelsMasks) \
                if ds.labelsMasks else None
            if getattr(ds, "featuresMasks", None):
                fmask = tuple(m.jax if m is not None else None
                              for m in ds.featuresMasks)
        else:
            inputs = (ds.features.jax.astype(self._dtype),)
            labels = (ds.labels.jax,)
            masks = (ds.labelsMask.jax,) if ds.labelsMask is not None else None
            if ds.featuresMask is not None:
                fmask = (ds.featuresMask.jax,)
        return float(self._scoreFn(self.params_, self.state_, inputs, labels,
                                   masks, fmask))

    def evaluate(self, it: DataSetIterator) -> Evaluation:
        ev = Evaluation()
        it.reset()
        while it.hasNext():
            # etl_fetch also consumes async-prefetch waits noted in
            # hasNext (see MultiLayerNetwork.evaluate)
            ds = etl_fetch(it)
            out = self.output(ds.features, featuresMask=ds.featuresMask)
            if isinstance(out, list):
                out = out[0]
            # jaxlint: sync-ok -- evaluation is host-side by contract (metrics math in numpy)
            ev.eval(ds.labels.numpy(), out.numpy(),
                    # jaxlint: disable=host-sync -- same evaluation D2H as the line above
                    ds.labelsMask.numpy() if getattr(ds, "labelsMask", None)
                    is not None else None)
        it.reset()
        return ev

    # -- listeners / params (same surface as MLN) -----------------------
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

    def params(self) -> NDArray:
        """Flattened param vector as a DEVICE-RESIDENT view (one
        jnp.concatenate, no host sync — see MultiLayerNetwork.params)."""
        chunks = []
        for name in self.conf.topoOrder:
            if name in (self.params_ or {}):
                for _path, _pname, v in _iter_leaf_params(self.params_[name]):
                    chunks.append(_ravel_replicated(v))
        return NDArray(jnp.concatenate(chunks) if chunks
                       else jnp.zeros((0,)))

    def setParams(self, flat) -> None:
        vec = jnp.ravel(flat.jax if isinstance(flat, NDArray)
                        else jnp.asarray(flat))
        pos = 0
        for name in self.conf.topoOrder:
            if name in self.params_:
                for path, _pname, cur in _iter_leaf_params(self.params_[name]):
                    n = int(np.prod(cur.shape))
                    _set_leaf(self.params_[name], path,
                              vec[pos:pos + n].reshape(cur.shape)
                              .astype(cur.dtype))
                    pos += n

    def numParams(self) -> int:
        return int(sum(int(np.prod(v.shape))
                       for v in jax.tree_util.tree_leaves(self.params_ or {})))

    def paramTable(self) -> Dict[str, NDArray]:
        return {f"{name}_{k}": NDArray(v)
                for name, lp in self.params_.items() for k, v in lp.items()}

    def getEpochCount(self) -> int:
        return self.epochCount

    def getNumLayers(self) -> int:
        return sum(1 for n, _ in self.conf.nodes.values()
                   if isinstance(n, Layer))

    def summary(self) -> str:
        lines = [f"{'vertex':<24} {'type':<26} {'params':>10} inputs"]
        total = 0
        for name in self.conf.topoOrder:
            node, ins = self.conf.nodes[name]
            n = sum(int(np.prod(v.shape)) for _p, _k, v in
                    _iter_leaf_params((self.params_ or {}).get(name, {})))
            total += n
            lines.append(f"{name:<24} {type(node).__name__:<26} {n:>10} {ins}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)
