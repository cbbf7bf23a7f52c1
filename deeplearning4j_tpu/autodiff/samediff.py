"""SameDiff — define-by-graph autodiff, the ND4J graph API rebuilt TPU-first.

Reference: nd4j-api ``org/nd4j/autodiff/samediff/SameDiff.java`` (graph +
variable table + sessions), ``org/nd4j/autodiff/samediff/ops/*.java`` (op
namespaces ``sd.math()``/``sd.nn()``/``sd.cnn()``/``sd.rnn()``/``sd.loss()``),
``org/nd4j/autodiff/functions/DifferentialFunction.java`` (per-op ``doDiff``)
and ``org/nd4j/autodiff/samediff/internal/{InferenceSession,TrainingSession}``
(SURVEY.md §2.3, §3.3).

TPU-first design (SURVEY.md §7.1): the graph is a *light* Python DAG kept only
for (a) the define-by-graph user API, (b) TF/Keras import and (c) serde.
Execution does NOT interpret the DAG op-by-op the way ``InferenceSession``
does — the whole graph is staged into one pure function and ``jax.jit``
compiles it to a single XLA executable per placeholder-shape signature.
Autodiff is ``jax.grad`` of that staged function, replacing the reference's
``createGradFunction``/per-op ``doDiff`` grad-graph construction.  TF-style
control flow (Enter/Exit/Switch/Merge — interpreted in Java in the
reference, §3.3) becomes structured ``lax.cond``/``lax.while_loop`` ops.
"""
from __future__ import annotations

import functools
import io
import json
import zipfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.learning.config import IUpdater, Adam
from deeplearning4j_tpu.ops.ndarray import NDArray

__all__ = ["SameDiff", "SDVariable", "VariableType", "TrainingConfig",
           "register_op"]


class VariableType:
    VARIABLE = "VARIABLE"        # trainable parameter
    CONSTANT = "CONSTANT"        # fixed array
    PLACEHOLDER = "PLACEHOLDER"  # fed at exec time
    ARRAY = "ARRAY"              # op output


# ---------------------------------------------------------------------------
# Op registry: op name -> (attrs -> callable(*arrays) -> array | tuple).
# The registry is the serde + import boundary: graph.json stores (op, attrs)
# and the importer emits the same names (reference analogue: libnd4j
# OpRegistrator name->DeclarableOp lookup, include/ops/declarable/
# OpRegistrator.h).
# ---------------------------------------------------------------------------
OP_IMPLS: Dict[str, Callable[..., Callable]] = {}


def register_op(name: str):
    def deco(factory):
        OP_IMPLS[name] = factory
        return factory
    return deco


def _simple(name, fn):
    OP_IMPLS[name] = lambda **attrs: fn


def _axis_op(name, fn):
    def factory(dims=None, keepDims=False, **_):
        ax = tuple(dims) if dims is not None else None
        return lambda x: fn(x, axis=ax, keepdims=bool(keepDims))
    OP_IMPLS[name] = factory


# arithmetic / pairwise --------------------------------------------------
_simple("add", jnp.add)
_simple("sub", jnp.subtract)
_simple("mul", jnp.multiply)
_simple("div", jnp.divide)
_simple("rsub", lambda x, y: y - x)
_simple("rdiv", lambda x, y: y / x)
_simple("pow", jnp.power)
_simple("floordiv", jnp.floor_divide)
_simple("mod", jnp.mod)
_simple("squaredDifference", lambda x, y: (x - y) ** 2)
_simple("max_pairwise", jnp.maximum)
_simple("min_pairwise", jnp.minimum)
_simple("atan2", jnp.arctan2)
# transforms -------------------------------------------------------------
for _n, _f in [("neg", jnp.negative), ("exp", jnp.exp), ("log", jnp.log),
               ("log1p", jnp.log1p), ("sqrt", jnp.sqrt), ("square", jnp.square),
               ("abs", jnp.abs), ("sign", jnp.sign), ("floor", jnp.floor),
               ("ceil", jnp.ceil), ("round", jnp.round), ("sin", jnp.sin),
               ("cos", jnp.cos), ("tan", jnp.tan), ("asin", jnp.arcsin),
               ("acos", jnp.arccos), ("atan", jnp.arctan), ("sinh", jnp.sinh),
               ("cosh", jnp.cosh), ("tanh", jnp.tanh),
               ("erf", jax.scipy.special.erf), ("erfc", jax.scipy.special.erfc),
               ("sigmoid", jax.nn.sigmoid), ("softplus", jax.nn.softplus),
               ("softsign", jax.nn.soft_sign), ("relu6", jax.nn.relu6),
               ("elu", jax.nn.elu), ("selu", jax.nn.selu),
               ("swish", jax.nn.silu), ("mish", jax.nn.mish),
               ("gelu", jax.nn.gelu), ("hardSigmoid", jax.nn.hard_sigmoid),
               ("hardTanh", lambda x: jnp.clip(x, -1.0, 1.0)),
               ("reciprocal", jnp.reciprocal), ("rsqrt", lax.rsqrt),
               ("identity", lambda x: x), ("logSigmoid", jax.nn.log_sigmoid),
               ("isNaN", jnp.isnan), ("isInf", jnp.isinf),
               ("isFinite", jnp.isfinite)]:
    _simple(_n, _f)


@register_op("gelu")
def _gelu_op(approximate=True, **_):
    # overrides the _simple registration: ONNX opset-20 Gelu (and torch
    # nn.GELU) default to the exact erf form — the attr must reach the
    # kernel (default stays tanh-approx, the BERT/reference convention)
    return lambda x: jax.nn.gelu(x, approximate=bool(approximate))


@register_op("relu")
def _relu(cutoff=0.0, **_):
    return lambda x: jnp.where(x > cutoff, x, 0.0)


@register_op("leakyRelu")
def _leaky(alpha=0.01, **_):
    return lambda x: jax.nn.leaky_relu(x, alpha)


@register_op("clipByValue")
def _clipv(clipValueMin=0.0, clipValueMax=0.0, **_):
    return lambda x: jnp.clip(x, clipValueMin, clipValueMax)


@register_op("softmax")
def _softmax(dimension=-1, **_):
    return lambda x: jax.nn.softmax(x, axis=dimension)


@register_op("logSoftmax")
def _logsoftmax(dimension=-1, **_):
    return lambda x: jax.nn.log_softmax(x, axis=dimension)


@register_op("cast")
def _cast(dtype="float32", **_):
    return lambda x: x.astype(jnp.dtype(dtype))


# reductions -------------------------------------------------------------
_axis_op("sum", jnp.sum)
_axis_op("mean", jnp.mean)
_axis_op("reduce_max", jnp.max)
_axis_op("reduce_min", jnp.min)
_axis_op("prod", jnp.prod)
# Nd4j std/variance default to biasCorrected=true (ddof=1), unlike numpy
_axis_op("std", functools.partial(jnp.std, ddof=1))
_axis_op("variance", functools.partial(jnp.var, ddof=1))
_axis_op("any", jnp.any)
_axis_op("all", jnp.all)
_axis_op("countNonZero", lambda x, axis, keepdims: jnp.sum(
    (x != 0).astype(jnp.int32), axis=axis, keepdims=keepdims))


@register_op("norm1")
def _norm1(dims=None, keepDims=False, **_):
    ax = tuple(dims) if dims is not None else None
    return lambda x: jnp.sum(jnp.abs(x), axis=ax, keepdims=keepDims)


@register_op("norm2")
def _norm2(dims=None, keepDims=False, **_):
    ax = tuple(dims) if dims is not None else None
    return lambda x: jnp.sqrt(jnp.sum(x * x, axis=ax, keepdims=keepDims))


@register_op("normMax")
def _normmax(dims=None, keepDims=False, **_):
    ax = tuple(dims) if dims is not None else None
    return lambda x: jnp.max(jnp.abs(x), axis=ax, keepdims=keepDims)


@register_op("argmax")
def _argmax(dimension=0, keepDims=False, **_):
    return lambda x: jnp.argmax(x, axis=dimension, keepdims=keepDims)


@register_op("argmin")
def _argmin(dimension=0, keepDims=False, **_):
    return lambda x: jnp.argmin(x, axis=dimension, keepdims=keepDims)


@register_op("cumsum")
def _cumsum(axis=0, **_):
    return lambda x: jnp.cumsum(x, axis=axis)


@register_op("cumprod")
def _cumprod(axis=0, **_):
    return lambda x: jnp.cumprod(x, axis=axis)


# blas / linalg ----------------------------------------------------------
@register_op("mmul")
def _mmul(transposeA=False, transposeB=False, **_):
    def fn(a, b):
        if transposeA:
            a = jnp.swapaxes(a, -1, -2)
        if transposeB:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)
    return fn


_simple("tensorMmul", jnp.matmul)
_simple("dot", lambda a, b: jnp.sum(a * b, axis=-1))


# shape ------------------------------------------------------------------
@register_op("reshape")
def _reshape(shape=(), **_):
    return lambda x: jnp.reshape(x, tuple(int(s) for s in shape))


@register_op("permute")
def _permute(dims=(), **_):
    return lambda x: jnp.transpose(x, tuple(dims))


_simple("transpose", lambda x: jnp.swapaxes(x, -1, -2)
        if x.ndim >= 2 else x)


@register_op("expandDims")
def _expand(axis=0, **_):
    return lambda x: jnp.expand_dims(x, axis)


@register_op("squeeze")
def _squeeze(axis=None, **_):
    return lambda x: jnp.squeeze(x, axis=axis)


@register_op("concat")
def _concat(dimension=0, **_):
    return lambda *xs: jnp.concatenate(xs, axis=dimension)


@register_op("stack")
def _stack(axis=0, **_):
    return lambda *xs: jnp.stack(xs, axis=axis)


@register_op("unstack")
def _unstack(axis=0, num=None, **_):
    def fn(x):
        parts = jnp.split(x, x.shape[axis], axis=axis)
        return tuple(jnp.squeeze(p, axis=axis) for p in parts)
    return fn


@register_op("tile")
def _tile(reps=(), **_):
    return lambda x: jnp.tile(x, tuple(reps))


@register_op("slice")
def _slice(begin=(), size=(), **_):
    def fn(x):
        ends = [b + s if s >= 0 else x.shape[i]
                for i, (b, s) in enumerate(zip(begin, size))]
        return x[tuple(slice(b, e) for b, e in zip(begin, ends))]
    return fn


@register_op("stridedSlice")
def _strided(begin=(), end=(), strides=None, axes=None, **_):
    def fn(x):
        st = strides or [1] * len(begin)
        ax = axes if axes is not None else list(range(len(begin)))
        sl = [slice(None)] * x.ndim
        for a, b, e, s_ in zip(ax, begin, end, st):
            # None = open end (TF mask semantics); non-negative ends clamp
            # to the dim (ONNX INT64_MAX "to the end" sentinels)
            if e is not None:
                e = min(int(e), x.shape[int(a)]) if int(e) >= 0 else int(e)
            b = None if b is None else int(b)
            sl[int(a)] = slice(b, e, int(s_))
        return x[tuple(sl)]
    return fn


@register_op("gather")
def _gather(axis=0, **_):
    return lambda x, idx: jnp.take(x, idx.astype(jnp.int32), axis=axis)


@register_op("scatterUpdate")
def _scatter_upd(**_):
    return lambda ref, idx, upd: ref.at[idx.astype(jnp.int32)].set(upd)


@register_op("scatterAdd")
def _scatter_add(**_):
    return lambda ref, idx, upd: ref.at[idx.astype(jnp.int32)].add(upd)


@register_op("reverse")
def _reverse(dims=(0,), **_):
    return lambda x: jnp.flip(x, axis=tuple(dims))


@register_op("pad")
def _pad(paddings=(), constant=0.0, mode="CONSTANT", **_):
    m = {"CONSTANT": "constant", "REFLECT": "reflect",
         "SYMMETRIC": "symmetric"}[mode]
    def fn(x):
        pw = tuple(tuple(p) for p in paddings)
        if m == "constant":
            return jnp.pad(x, pw, mode=m, constant_values=constant)
        return jnp.pad(x, pw, mode=m)
    return fn


@register_op("oneHot")
def _onehot(depth=2, on=1.0, off=0.0, axis=-1, **_):
    return lambda x: jax.nn.one_hot(
        x.astype(jnp.int32), depth, axis=axis) * (on - off) + off


_simple("shape_of", lambda x: jnp.asarray(x.shape, dtype=jnp.int64))
_simple("size", lambda x: jnp.asarray(x.size, dtype=jnp.int64))
_simple("rank", lambda x: jnp.asarray(x.ndim, dtype=jnp.int32))
_simple("zerosLike", jnp.zeros_like)
_simple("onesLike", jnp.ones_like)


@register_op("fill")
def _fill(shape=(), value=0.0, dtype="float32", **_):
    return lambda: jnp.full(tuple(shape), value, dtype=jnp.dtype(dtype))


@register_op("range")
def _range(start=0.0, limit=1.0, delta=1.0, dtype="float32", **_):
    return lambda: jnp.arange(start, limit, delta, dtype=jnp.dtype(dtype))


@register_op("linspace")
def _linspace(start=0.0, stop=1.0, num=10, **_):
    return lambda: jnp.linspace(start, stop, num)


@register_op("eye")
def _eye(rows=1, cols=None, **_):
    return lambda: jnp.eye(rows, cols)


# comparison / select ----------------------------------------------------
_simple("eq", lambda x, y: (x == y))
_simple("neq", lambda x, y: (x != y))
_simple("gt", lambda x, y: (x > y))
_simple("gte", lambda x, y: (x >= y))
_simple("lt", lambda x, y: (x < y))
_simple("lte", lambda x, y: (x <= y))
_simple("and_", jnp.logical_and)
_simple("or_", jnp.logical_or)
_simple("xor", jnp.logical_xor)
_simple("not_", jnp.logical_not)
_simple("where", jnp.where)
_simple("select", jnp.where)


# segment / misc ---------------------------------------------------------
@register_op("matrixDiag")
def _mdiag(**_):
    return jnp.diag


@register_op("trace")
def _trace(**_):
    return jnp.trace


# nn ---------------------------------------------------------------------
@register_op("linear")
def _linear(**_):
    return lambda x, w, b: jnp.matmul(x, w) + b


@register_op("reluLayer")
def _relu_layer(**_):
    return lambda x, w, b: jax.nn.relu(jnp.matmul(x, w) + b)


@register_op("layerNorm")
def _layernorm(axis=-1, eps=1e-5, noBias=False, **_):
    def fn(x, g, *b):
        mu = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.var(x, axis=axis, keepdims=True)
        y = (x - mu) * lax.rsqrt(var + eps) * g
        return y if (noBias or not b) else y + b[0]
    return fn


@register_op("batchNorm")
def _batchnorm(axis=1, eps=1e-5, **_):
    def fn(x, mean, var, gamma, beta):
        shp = [1] * x.ndim
        shp[axis] = -1
        rs = lambda a: jnp.reshape(a, shp)
        return (x - rs(mean)) * lax.rsqrt(rs(var) + eps) * rs(gamma) + rs(beta)
    return fn


@register_op("dropout")
def _dropout(p=0.5, seed=0, **_):
    # p is the RETAIN probability, matching ND4J DropOutInverted semantics.
    # Takes the implicit per-step iteration counter (threaded by _build_fn)
    # so each train step draws a fresh mask; identity at inference.
    def fn(x, it):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), it)
        mask = jax.random.bernoulli(key, p, x.shape)
        return jnp.where(mask, x / p, 0.0)
    return fn


RNG_TRAIN_OPS = {"dropout"}  # identity at inference; fresh key per step


@register_op("conv2d")
def _conv2d(kH=1, kW=1, sH=1, sW=1, pH=0, pW=0, dH=1, dW=1,
            isSameMode=False, dataFormat="NCHW", **_):
    def fn(x, w, *b):
        # w: (kH, kW, inC, outC) — ND4J conv weight layout for SameDiff cnn()
        pad = "SAME" if isSameMode else [(pH, pH), (pW, pW)]
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        (dataFormat, "HWIO", dataFormat))
        y = lax.conv_general_dilated(x, w, (sH, sW), pad,
                                     rhs_dilation=(dH, dW),
                                     dimension_numbers=dn)
        if b:
            bias = b[0].reshape((1, -1, 1, 1) if dataFormat == "NCHW"
                                else (1, 1, 1, -1))
            y = y + bias
        return y
    return fn


def _pool_dims(kH, kW, sH, sW, pH, pW, dataFormat):
    if dataFormat == "NHWC":
        return (1, kH, kW, 1), (1, sH, sW, 1), \
            ((0, 0), (pH, pH), (pW, pW), (0, 0))
    return (1, 1, kH, kW), (1, 1, sH, sW), \
        ((0, 0), (0, 0), (pH, pH), (pW, pW))


@register_op("maxPooling2d")
def _maxpool2d(kH=2, kW=2, sH=2, sW=2, pH=0, pW=0, isSameMode=False,
               dataFormat="NCHW", **_):
    win, stride, pad = _pool_dims(kH, kW, sH, sW, pH, pW, dataFormat)
    def fn(x):
        p = "SAME" if isSameMode else pad
        return lax.reduce_window(x, -jnp.inf, lax.max, win, stride, p)
    return fn


@register_op("avgPooling2d")
def _avgpool2d(kH=2, kW=2, sH=2, sW=2, pH=0, pW=0, isSameMode=False,
               dataFormat="NCHW", **_):
    win, stride, pad = _pool_dims(kH, kW, sH, sW, pH, pW, dataFormat)
    def fn(x):
        p = "SAME" if isSameMode else pad
        s = lax.reduce_window(x, 0.0, lax.add, win, stride, p)
        n = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, win, stride, p)
        return s / n
    return fn


@register_op("embeddingLookup")
def _embed(**_):
    return lambda table, ids: jnp.take(table, ids.astype(jnp.int32), axis=0)


@register_op("dotProductAttention")
def _dpa(scaled=True, withWeights=False, **_):
    # Reference: libnd4j ops/declarable/generic/nn/dot_product_attention.cpp
    def fn(q, k, v, *mask):
        d = q.shape[-1]
        scores = jnp.einsum("...qd,...kd->...qk", q, k)
        if scaled:
            scores = scores / jnp.sqrt(jnp.asarray(d, scores.dtype))
        if mask:
            scores = jnp.where(mask[0].astype(bool), scores, -1e9)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("...qk,...kd->...qd", w, v)
        return (out, w) if withWeights else out
    return fn


@register_op("multiHeadDotProductAttention")
def _mhdpa(nHeads=1, scaled=True, **_):
    # Reference: libnd4j multi_head_dot_product_attention.cpp (SURVEY §5.7).
    # Inputs q,k,v: (b, t, dModel); Wq/Wk/Wv: (dModel, nHeads*dHead);
    # Wo: (nHeads*dHead, dModel).  One einsum chain -> MXU-friendly.
    def fn(q, k, v, Wq, Wk, Wv, Wo, *mask):
        b, tq, _ = q.shape
        tk = k.shape[1]
        def proj(x, w):
            y = jnp.matmul(x, w)
            return y.reshape(b, x.shape[1], nHeads, -1).transpose(0, 2, 1, 3)
        qh, kh, vh = proj(q, Wq), proj(k, Wk), proj(v, Wv)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh)
        if scaled:
            scores = scores / jnp.sqrt(jnp.asarray(qh.shape[-1], scores.dtype))
        if mask:
            m = mask[0].astype(bool).reshape(b, 1, 1, tk)
            scores = jnp.where(m, scores, -1e9)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
        out = out.transpose(0, 2, 1, 3).reshape(b, tq, -1)
        return jnp.matmul(out, Wo)
    return fn


# losses -----------------------------------------------------------------
def _reduce_loss(per_ex, reduction, w=None):
    """LossReduce semantics (reference: org/nd4j/autodiff/loss/LossReduce).

    With weights, MEAN_BY_NONZERO_WEIGHT_COUNT divides by the number of
    non-zero weights (the masked-LM convention), MEAN_BY_WEIGHT by sum(w).
    """
    if w is not None:
        per_ex = per_ex * w
        w = jnp.broadcast_to(w, per_ex.shape)  # count broadcast elements
    if reduction == "NONE":
        return per_ex
    if reduction == "SUM":
        return jnp.sum(per_ex)
    if w is None:
        return jnp.mean(per_ex)
    if reduction == "MEAN_BY_WEIGHT":
        return jnp.sum(per_ex) / jnp.maximum(jnp.sum(w), 1e-9)
    nz = jnp.sum((w != 0).astype(per_ex.dtype))
    return jnp.sum(per_ex) / jnp.maximum(nz, 1.0)


@register_op("softmaxCrossEntropy")
def _sce(reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", labelSmoothing=0.0, **_):
    def fn(logits, labels, *w):
        if labelSmoothing:
            n = labels.shape[-1]
            labels = labels * (1.0 - labelSmoothing) + labelSmoothing / n
        per = -jnp.sum(labels * jax.nn.log_softmax(logits, -1), axis=-1)
        return _reduce_loss(per, reduction, w[0] if w else None)
    return fn


@register_op("sparseSoftmaxCrossEntropy")
def _ssce(reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(logits, labels, *w):
        lp = jax.nn.log_softmax(logits, -1)
        per = -jnp.take_along_axis(
            lp, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
        return _reduce_loss(per, reduction, w[0] if w else None)
    return fn


@register_op("sigmoidCrossEntropy")
def _sigce(reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(logits, labels, *w):
        per = jnp.mean(
            jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))), axis=-1)
        return _reduce_loss(per, reduction, w[0] if w else None)
    return fn


@register_op("meanSquaredError")
def _mse_loss(reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(pred, labels, *w):
        per = jnp.mean((pred - labels) ** 2, axis=-1)
        return _reduce_loss(per, reduction, w[0] if w else None)
    return fn


@register_op("absoluteDifference")
def _l1_loss(reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(pred, labels, *w):
        per = jnp.mean(jnp.abs(pred - labels), axis=-1)
        return _reduce_loss(per, reduction, w[0] if w else None)
    return fn


@register_op("huberLoss")
def _huber(delta=1.0, reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(pred, labels, *w):
        e = jnp.abs(pred - labels)
        per = jnp.mean(jnp.where(e <= delta, 0.5 * e * e,
                                 delta * e - 0.5 * delta * delta), axis=-1)
        return _reduce_loss(per, reduction)
    return fn


@register_op("logLoss")
def _logloss(eps=1e-7, reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(pred, labels):
        p = jnp.clip(pred, eps, 1.0 - eps)
        per = -jnp.mean(labels * jnp.log(p)
                        + (1 - labels) * jnp.log(1 - p), axis=-1)
        return _reduce_loss(per, reduction)
    return fn


@register_op("cosineDistance")
def _cosdist(dimension=-1, reduction="MEAN_BY_NONZERO_WEIGHT_COUNT", **_):
    def fn(pred, labels):
        per = 1.0 - jnp.sum(pred * labels, axis=dimension)
        return _reduce_loss(per, reduction)
    return fn


# random (counter-based: seeded per node, reproducible under jit) --------
@register_op("random_normal")
def _rnormal(shape=(), seed=0, mean=0.0, stddev=1.0, **_):
    return lambda: mean + stddev * jax.random.normal(
        jax.random.PRNGKey(seed), tuple(shape))


@register_op("random_uniform")
def _runiform(shape=(), seed=0, minVal=0.0, maxVal=1.0, **_):
    return lambda: jax.random.uniform(
        jax.random.PRNGKey(seed), tuple(shape), minval=minVal, maxval=maxVal)


@register_op("random_bernoulli")
def _rbern(shape=(), seed=0, p=0.5, **_):
    return lambda: jax.random.bernoulli(
        jax.random.PRNGKey(seed), p, tuple(shape)).astype(jnp.float32)


# control flow (reference: TF-style Enter/Exit/Switch/Merge interpreted in
# AbstractSession — here lax regions compiled INTO the executable) ----------
@register_op("while_loop")
def _while_impl(cond_fn=None, body_fn=None, n=1, **_):
    def fn(*args):
        def c(carry):
            return cond_fn(*carry)[0].astype(bool).reshape(())

        def b(carry):
            return tuple(body_fn(*carry))

        out = lax.while_loop(c, b, tuple(args))
        return out if n > 1 else out[0]

    return fn


@register_op("if_cond")
def _if_impl(cond_fn=None, true_fn=None, false_fn=None, n_out=1, **_):
    def fn(*args):
        pred = cond_fn(*args)[0].astype(bool).reshape(())
        out = lax.cond(pred, lambda a: tuple(true_fn(*a)),
                       lambda a: tuple(false_fn(*a)), tuple(args))
        return out if n_out > 1 else out[0]

    return fn


@register_op("for_loop")
def _for_impl(body_fn=None, n=1, iterations=1, **_):
    def fn(*args):
        def step(carry, _):
            return tuple(body_fn(*carry)), None

        out, _ = lax.scan(step, tuple(args), None, length=iterations)
        return out if n > 1 else out[0]

    return fn


# ---------------------------------------------------------------------------
# SDVariable
# ---------------------------------------------------------------------------
class SDVariable:
    """Symbolic variable (reference: org/nd4j/autodiff/samediff/SDVariable)."""

    def __init__(self, sd: "SameDiff", name: str, varType: str,
                 shape=None, dtype=None):
        self.sd = sd
        self._name = name
        self.variableType = varType
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype

    def name(self) -> str:
        return self._name

    def rename(self, newName: str) -> "SDVariable":
        return self.sd.renameVariable(self._name, newName)

    # -- arithmetic (each records a graph op) --
    def _bin(self, op, other, rev=False):
        o = other if isinstance(other, SDVariable) else self.sd.constant(other)
        a, b = (o, self) if rev else (self, o)
        return self.sd._op(op, [a, b])

    def add(self, o): return self._bin("add", o)
    def sub(self, o): return self._bin("sub", o)
    def mul(self, o): return self._bin("mul", o)
    def div(self, o): return self._bin("div", o)
    def rsub(self, o): return self._bin("sub", o, rev=True)
    def rdiv(self, o): return self._bin("div", o, rev=True)
    def pow(self, o): return self._bin("pow", o)
    __add__ = add
    __radd__ = add
    __sub__ = sub
    __rsub__ = rsub
    __mul__ = mul
    __rmul__ = mul
    __truediv__ = div
    __rtruediv__ = rdiv
    __pow__ = pow

    def __neg__(self): return self.sd._op("neg", [self])

    def neg(self): return -self

    def mmul(self, o, transposeA=False, transposeB=False):
        return self.sd._op("mmul", [self, o],
                           {"transposeA": transposeA, "transposeB": transposeB})

    def __matmul__(self, o): return self.mmul(o)

    # comparisons
    def eq(self, o): return self._bin("eq", o)
    def neq(self, o): return self._bin("neq", o)
    def gt(self, o): return self._bin("gt", o)
    def gte(self, o): return self._bin("gte", o)
    def lt(self, o): return self._bin("lt", o)
    def lte(self, o): return self._bin("lte", o)

    # reductions / transforms
    def _red(self, op, dims, keepDims):
        if dims is not None and not isinstance(dims, (list, tuple)):
            dims = (dims,)
        return self.sd._op(op, [self], {"dims": dims, "keepDims": keepDims})

    def sum(self, *dims, keepDims=False):
        return self._red("sum", dims or None, keepDims)

    def mean(self, *dims, keepDims=False):
        return self._red("mean", dims or None, keepDims)

    def max(self, *dims, keepDims=False):
        return self._red("reduce_max", dims or None, keepDims)

    def min(self, *dims, keepDims=False):
        return self._red("reduce_min", dims or None, keepDims)

    def std(self, *dims, keepDims=False):
        return self._red("std", dims or None, keepDims)

    def prod(self, *dims, keepDims=False):
        return self._red("prod", dims or None, keepDims)

    def norm1(self, *dims): return self._red("norm1", dims or None, False)
    def norm2(self, *dims): return self._red("norm2", dims or None, False)

    def argmax(self, dimension=0):
        return self.sd._op("argmax", [self], {"dimension": dimension})

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return self.sd._op("reshape", [self], {"shape": shape})

    def permute(self, *dims):
        return self.sd._op("permute", [self], {"dims": dims})

    def transpose(self):
        return self.sd._op("transpose", [self])

    def castTo(self, dtype):
        return self.sd._op("cast", [self], {"dtype": str(dtype)})

    def get(self, *slices):
        """Static slicing (NDArrayIndex.interval analogue)."""
        begin, end, strides = [], [], []
        for s in slices:
            if isinstance(s, slice):
                begin.append(s.start or 0)
                end.append(s.stop)
                strides.append(s.step or 1)
            else:
                begin.append(int(s))
                end.append(int(s) + 1)
                strides.append(1)
        return self.sd._op("stridedSlice", [self],
                           {"begin": begin, "end": end, "strides": strides})

    __getitem__ = get

    # -- graph state --
    def markAsLoss(self):
        self.sd.setLossVariables(self._name, extend=True)
        return self

    def getArr(self) -> Optional[NDArray]:
        v = self.sd._arrays.get(self._name)
        return NDArray(v) if v is not None else None

    def setArray(self, arr):
        self.sd.setArrayForVariable(self._name, arr)

    def eval(self, placeholders: Optional[Dict] = None) -> NDArray:
        return self.sd.output(placeholders or {}, self._name)[self._name]

    def gradient(self) -> Optional[NDArray]:
        g = self.sd._last_grads.get(self._name)
        return NDArray(g) if g is not None else None

    def __repr__(self):
        return (f"SDVariable(name={self._name!r}, "
                f"type={self.variableType}, shape={self.shape})")


# ---------------------------------------------------------------------------
# Op namespaces (sd.math() etc. — reference org/nd4j/autodiff/samediff/ops/)
# ---------------------------------------------------------------------------
class _Namespace:
    def __init__(self, sd: "SameDiff"):
        self.sd = sd


def _ns_unary(op):
    def m(self, x: SDVariable, name: str = None):
        return self.sd._op(op, [x], name=name)
    return m


def _ns_binary(op):
    def m(self, x: SDVariable, y, name: str = None):
        y = y if isinstance(y, SDVariable) else self.sd.constant(y)
        return self.sd._op(op, [x, y], name=name)
    return m


class SDMath(_Namespace):
    for _o in ["exp", "log", "log1p", "sqrt", "square", "abs", "sign",
               "floor", "ceil", "round", "sin", "cos", "tan", "asin", "acos",
               "atan", "sinh", "cosh", "tanh", "erf", "erfc", "neg",
               "reciprocal", "rsqrt", "isNaN", "isInf", "isFinite",
               "cumsum", "cumprod", "trace"]:
        locals()[_o] = _ns_unary(_o)
    for _o in ["add", "sub", "mul", "div", "pow", "atan2", "mod",
               "squaredDifference"]:
        locals()[_o] = _ns_binary(_o)
    max = _ns_binary("max_pairwise")
    min = _ns_binary("min_pairwise")
    and_ = _ns_binary("and_")
    or_ = _ns_binary("or_")
    xor = _ns_binary("xor")
    not_ = _ns_unary("not_")
    del _o

    def clipByValue(self, x, lo, hi, name=None):
        return self.sd._op("clipByValue", [x],
                           {"clipValueMin": lo, "clipValueMax": hi}, name=name)


class SDNN(_Namespace):
    for _o in ["sigmoid", "softplus", "softsign", "elu", "selu", "swish",
               "mish", "gelu", "relu6", "hardSigmoid", "hardTanh",
               "logSigmoid", "tanh"]:
        locals()[_o] = _ns_unary(_o)
    del _o

    def relu(self, x, cutoff=0.0, name=None):
        return self.sd._op("relu", [x], {"cutoff": cutoff}, name=name)

    def leakyRelu(self, x, alpha=0.01, name=None):
        return self.sd._op("leakyRelu", [x], {"alpha": alpha}, name=name)

    def softmax(self, x, dimension=-1, name=None):
        return self.sd._op("softmax", [x], {"dimension": dimension}, name=name)

    def logSoftmax(self, x, dimension=-1, name=None):
        return self.sd._op("logSoftmax", [x], {"dimension": dimension},
                           name=name)

    def linear(self, x, w, b, name=None):
        return self.sd._op("linear", [x, w, b], name=name)

    def reluLayer(self, x, w, b, name=None):
        return self.sd._op("reluLayer", [x, w, b], name=name)

    def layerNorm(self, x, gain, bias=None, axis=-1, name=None):
        ins = [x, gain] + ([bias] if bias is not None else [])
        return self.sd._op("layerNorm", ins,
                           {"axis": axis, "noBias": bias is None}, name=name)

    def batchNorm(self, x, mean, var, gamma, beta, eps=1e-5, axis=1,
                  name=None):
        return self.sd._op("batchNorm", [x, mean, var, gamma, beta],
                           {"axis": axis, "eps": eps}, name=name)

    def dropout(self, x, keepProb=0.5, seed=0, name=None):
        return self.sd._op("dropout", [x], {"p": keepProb, "seed": seed},
                           name=name)

    def dotProductAttention(self, q, k, v, mask=None, scaled=True, name=None):
        ins = [q, k, v] + ([mask] if mask is not None else [])
        return self.sd._op("dotProductAttention", ins, {"scaled": scaled},
                           name=name)

    def multiHeadDotProductAttention(self, q, k, v, Wq, Wk, Wv, Wo,
                                     mask=None, nHeads=1, scaled=True,
                                     name=None):
        ins = [q, k, v, Wq, Wk, Wv, Wo] + ([mask] if mask is not None else [])
        return self.sd._op("multiHeadDotProductAttention", ins,
                           {"nHeads": nHeads, "scaled": scaled}, name=name)

    def embeddingLookup(self, table, ids, name=None):
        return self.sd._op("embeddingLookup", [table, ids], name=name)

    def pad(self, x, paddings, constant=0.0, mode="CONSTANT", name=None):
        return self.sd._op("pad", [x], {"paddings": paddings,
                                        "constant": constant, "mode": mode},
                           name=name)


class SDCNN(_Namespace):
    def conv2d(self, x, w, b=None, kH=None, kW=None, sH=1, sW=1, pH=0, pW=0,
               dH=1, dW=1, isSameMode=False, dataFormat="NCHW", name=None):
        if kH is None:
            kH, kW = int(w.shape[0]), int(w.shape[1])
        ins = [x, w] + ([b] if b is not None else [])
        return self.sd._op("conv2d", ins,
                           {"kH": kH, "kW": kW, "sH": sH, "sW": sW,
                            "pH": pH, "pW": pW, "dH": dH, "dW": dW,
                            "isSameMode": isSameMode,
                            "dataFormat": dataFormat}, name=name)

    def maxPooling2d(self, x, kH=2, kW=2, sH=2, sW=2, pH=0, pW=0,
                     isSameMode=False, name=None):
        return self.sd._op("maxPooling2d", [x],
                           {"kH": kH, "kW": kW, "sH": sH, "sW": sW,
                            "pH": pH, "pW": pW, "isSameMode": isSameMode},
                           name=name)

    def avgPooling2d(self, x, kH=2, kW=2, sH=2, sW=2, pH=0, pW=0,
                     isSameMode=False, name=None):
        return self.sd._op("avgPooling2d", [x],
                           {"kH": kH, "kW": kW, "sH": sH, "sW": sW,
                            "pH": pH, "pW": pW, "isSameMode": isSameMode},
                           name=name)


class SDLoss(_Namespace):
    def softmaxCrossEntropy(self, label, logits, weights=None,
                            labelSmoothing=0.0, name=None):
        ins = [logits, label] + ([weights] if weights is not None else [])
        return self.sd._op("softmaxCrossEntropy", ins,
                           {"labelSmoothing": labelSmoothing},
                           name=name).markAsLoss()

    def sparseSoftmaxCrossEntropy(self, logits, labels, weights=None,
                                  name=None):
        ins = [logits, labels] + ([weights] if weights is not None else [])
        return self.sd._op("sparseSoftmaxCrossEntropy", ins,
                           name=name).markAsLoss()

    def sigmoidCrossEntropy(self, label, logits, weights=None, name=None):
        ins = [logits, label] + ([weights] if weights is not None else [])
        return self.sd._op("sigmoidCrossEntropy", ins, name=name).markAsLoss()

    def meanSquaredError(self, label, pred, weights=None, name=None):
        ins = [pred, label] + ([weights] if weights is not None else [])
        return self.sd._op("meanSquaredError", ins, name=name).markAsLoss()

    def absoluteDifference(self, label, pred, weights=None, name=None):
        ins = [pred, label] + ([weights] if weights is not None else [])
        return self.sd._op("absoluteDifference", ins, name=name).markAsLoss()

    def huberLoss(self, label, pred, delta=1.0, name=None):
        return self.sd._op("huberLoss", [pred, label], {"delta": delta},
                           name=name).markAsLoss()

    def logLoss(self, label, pred, name=None):
        return self.sd._op("logLoss", [pred, label], name=name).markAsLoss()

    def cosineDistance(self, label, pred, dimension=-1, name=None):
        return self.sd._op("cosineDistance", [pred, label],
                           {"dimension": dimension}, name=name).markAsLoss()


class SDRandom(_Namespace):
    def normal(self, mean, stddev, shape, seed=0, name=None):
        return self.sd._op("random_normal", [],
                           {"shape": shape, "seed": seed, "mean": mean,
                            "stddev": stddev}, name=name)

    def uniform(self, minVal, maxVal, shape, seed=0, name=None):
        return self.sd._op("random_uniform", [],
                           {"shape": shape, "seed": seed, "minVal": minVal,
                            "maxVal": maxVal}, name=name)

    def bernoulli(self, p, shape, seed=0, name=None):
        return self.sd._op("random_bernoulli", [],
                           {"shape": shape, "seed": seed, "p": p}, name=name)


# ---------------------------------------------------------------------------
# TrainingConfig
# ---------------------------------------------------------------------------
class TrainingConfig:
    """Reference: org/nd4j/autodiff/samediff/TrainingConfig.java."""

    def __init__(self, updater: Optional[IUpdater] = None,
                 dataSetFeatureMapping: Sequence[str] = (),
                 dataSetLabelMapping: Sequence[str] = (),
                 l1: float = 0.0, l2: float = 0.0,
                 minimize: bool = True, dataType: str = "FLOAT"):
        self.updater = updater or Adam()
        self.dataSetFeatureMapping = list(dataSetFeatureMapping)
        self.dataSetLabelMapping = list(dataSetLabelMapping)
        self.l1 = l1
        self.l2 = l2
        self.minimize = minimize
        # "BFLOAT16"/"HALF": bf16 compute with f32 master variables (same
        # mixed-precision contract as MultiLayerNetwork's dataType config)
        self.dataType = dataType

    class Builder:
        def __init__(self):
            self._kw = {}

        def updater(self, u):
            self._kw["updater"] = u
            return self

        def dataSetFeatureMapping(self, *names):
            self._kw["dataSetFeatureMapping"] = list(names)
            return self

        def dataSetLabelMapping(self, *names):
            self._kw["dataSetLabelMapping"] = list(names)
            return self

        def dataType(self, dt: str):
            self._kw["dataType"] = dt
            return self

        def l1(self, v):
            self._kw["l1"] = v
            return self

        def l2(self, v):
            self._kw["l2"] = v
            return self

        def minimize(self, v=True):
            self._kw["minimize"] = v
            return self

        def build(self):
            return TrainingConfig(**self._kw)


def _fetch_curve(losses):
    """ONE stacked device fetch for a loss curve.  float()-ing each
    per-step device scalar costs a host round trip per step, and blocks
    the dispatch of the next one."""
    return np.asarray(jnp.stack(losses)).tolist() if losses else []


def _to_np(x):
    """Coerce to something ``jnp.asarray`` stages for free.

    jax.Array values (including those inside NDArray, whose constructor
    already staged them on device) MUST pass through unchanged: an
    ``np.asarray`` here forces a device->host pull and the subsequent
    ``jnp.asarray`` a re-upload — a full batch round-trip per train step
    (measured: BERT-base B=256 at 265 ms/step vs 166 ms once removed)."""
    if isinstance(x, NDArray):
        x = x._value
    if isinstance(x, jax.Array):
        return x
    return np.asarray(x)


# ---------------------------------------------------------------------------
# SameDiff
# ---------------------------------------------------------------------------
class _OpNode:
    __slots__ = ("op", "name", "inputs", "outputs", "attrs")

    def __init__(self, op, name, inputs, outputs, attrs):
        self.op = op
        self.name = name
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = attrs


class SameDiff:
    """The graph container (reference: org/nd4j/autodiff/samediff/SameDiff)."""

    def __init__(self):
        self._vars: Dict[str, SDVariable] = {}
        self._ops: List[_OpNode] = []
        self._producer: Dict[str, Tuple[_OpNode, int]] = {}
        self._arrays: Dict[str, jnp.ndarray] = {}   # VARIABLE/CONSTANT values
        self._loss_vars: List[str] = []
        self._counter = 0
        self._fn_cache: Dict[Any, Any] = {}
        self._train_step = None
        self._opt_state = None
        self._training_config: Optional[TrainingConfig] = None
        self._last_grads: Dict[str, jnp.ndarray] = {}
        self.iterationCount = 0
        # namespaces
        self._listeners: List = []
        self._math = SDMath(self)
        self._nn = SDNN(self)
        self._cnn = SDCNN(self)
        self._loss = SDLoss(self)
        self._random = SDRandom(self)

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # namespaces (both method-call and property style work)
    def math(self): return self._math
    def nn(self): return self._nn
    def cnn(self): return self._cnn
    def loss(self): return self._loss
    def random(self): return self._random

    def image(self):
        if not hasattr(self, "_image"):
            from deeplearning4j_tpu.autodiff.ops_ext import SDImage
            self._image = SDImage(self)
        return self._image

    def rnn(self):
        if not hasattr(self, "_rnn"):
            from deeplearning4j_tpu.autodiff.ops_ext import SDRNN
            self._rnn = SDRNN(self)
        return self._rnn

    def linalg(self):
        if not hasattr(self, "_linalg"):
            from deeplearning4j_tpu.autodiff.ops_ext import SDLinalg
            self._linalg = SDLinalg(self)
        return self._linalg

    # ---------------- variable management ----------------
    def _unique(self, base: str) -> str:
        if base not in self._vars:
            return base
        i = 1
        while f"{base}_{i}" in self._vars:
            i += 1
        return f"{base}_{i}"

    def _register(self, name, varType, shape=None, dtype=None) -> SDVariable:
        v = SDVariable(self, name, varType, shape, dtype)
        self._vars[name] = v
        return v

    def placeholder(self, name: str, dtype=jnp.float32,
                    shape: Sequence[Optional[int]] = None) -> SDVariable:
        return self._register(self._unique(name), VariableType.PLACEHOLDER,
                              shape, dtype)

    def var(self, name: str, arr=None, shape=None,
            dtype=jnp.float32) -> SDVariable:
        """Trainable variable; ``arr`` gives the initial value."""
        name = self._unique(name)
        if arr is not None:
            a = jnp.asarray(_to_np(arr))
            self._arrays[name] = a
            return self._register(name, VariableType.VARIABLE, a.shape,
                                  a.dtype)
        a = jnp.zeros(tuple(shape), dtype)
        self._arrays[name] = a
        return self._register(name, VariableType.VARIABLE, a.shape, dtype)

    def constant(self, value, name: str = None) -> SDVariable:
        name = self._unique(name or f"const_{self._counter}")
        self._counter += 1
        # Bare python scalars must NOT become float64/int64 (the package
        # enables x64): one f64 constant silently promotes every downstream
        # op to f64, which the TPU EMULATES — ruinously slow and 2x memory.
        # Promotion keeps explicit f64 graphs f64 (f64 op f32 -> f64).
        if type(value) is float:   # NOT np.float64 (a float subclass):
            a = jnp.float32(value)  # explicit f64 scalars keep f64
        elif isinstance(value, bool):
            a = jnp.asarray(value)
        elif isinstance(value, int):
            a = jnp.int32(value) if -(2**31) <= value < 2**31 \
                else jnp.int64(value)
        else:
            a = jnp.asarray(_to_np(value))
        self._arrays[name] = a
        return self._register(name, VariableType.CONSTANT, a.shape, a.dtype)

    def zero(self, name, *shape):
        return self.constant(np.zeros(shape, np.float32), name=name)

    def one(self, name, *shape):
        return self.constant(np.ones(shape, np.float32), name=name)

    def getVariable(self, name: str) -> SDVariable:
        return self._vars[name]

    def hasVariable(self, name: str) -> bool:
        return name in self._vars

    def variables(self) -> List[SDVariable]:
        return list(self._vars.values())

    def variableMap(self) -> Dict[str, SDVariable]:
        return dict(self._vars)

    def renameVariable(self, old: str, new: str) -> SDVariable:
        v = self._vars.pop(old)
        v._name = new
        self._vars[new] = v
        if old in self._arrays:
            self._arrays[new] = self._arrays.pop(old)
        for node in self._ops:
            node.inputs = [new if i == old else i for i in node.inputs]
            node.outputs = [new if o == old else o for o in node.outputs]
        self._producer = {}
        for node in self._ops:
            for i, o in enumerate(node.outputs):
                self._producer[o] = (node, i)
        self._loss_vars = [new if n == old else n for n in self._loss_vars]
        self._fn_cache.clear()
        self._train_step = None
        return v

    def _invalidate(self):
        self._fn_cache.clear()
        self._train_step = None

    def setArrayForVariable(self, name: str, arr):
        self._arrays[name] = jnp.asarray(_to_np(arr))
        self._invalidate()

    def convertToConstant(self, var: SDVariable):
        var.variableType = VariableType.CONSTANT
        self._invalidate()
        return var

    def convertToVariable(self, var: SDVariable):
        var.variableType = VariableType.VARIABLE
        self._invalidate()
        return var

    def setLossVariables(self, *names, extend=False):
        names = [n.name() if isinstance(n, SDVariable) else n for n in names]
        if extend:
            self._loss_vars.extend(n for n in names
                                   if n not in self._loss_vars)
        else:
            self._loss_vars = list(names)

    def getLossVariables(self) -> List[str]:
        return list(self._loss_vars)

    # ---------------- graph building ----------------
    def _op(self, op: str, inputs: Sequence[SDVariable],
            attrs: Optional[Dict] = None, n_out: int = 1,
            name: str = None) -> Union[SDVariable, List[SDVariable]]:
        if op not in OP_IMPLS:
            raise ValueError(f"Unknown op: {op}")
        attrs = dict(attrs or {})
        base = name or op
        out_names = []
        for i in range(n_out):
            nm = self._unique(base if (i == 0 and n_out == 1)
                              else f"{base}:{i}")
            out_names.append(nm)
        node = _OpNode(op, out_names[0], [v.name() for v in inputs],
                       out_names, attrs)
        self._ops.append(node)
        outs = [self._register(nm, VariableType.ARRAY) for nm in out_names]
        for i, nm in enumerate(out_names):
            self._producer[nm] = (node, i)
        self._fn_cache.clear()
        self._train_step = None
        return outs[0] if n_out == 1 else outs

    # ---------------- control flow ----------------
    def _stage_subgraph(self, n_in: int, build):
        """Build a sub-SameDiff from a user lambda and stage it to a pure
        function [args] -> [outs].  This is the TPU lowering of the
        reference's TF-style control-flow machinery: where AbstractSession
        interprets Enter/Exit/Switch/Merge/NextIteration frames op-by-op IN
        JAVA (SURVEY §3.3), the subgraph here compiles INTO the parent's
        XLA executable as a lax control-flow region.

        Returns ``(staged, n_out, payload)`` — payload is the
        JSON-serializable description of the sub-graph (the analogue of
        the reference's FlatBuffers sub-graph regions,
        ``graph/scheme/*.fbs``) from which ``_restage_payload`` rebuilds
        the closure after ``SameDiff.load``."""
        sub = SameDiff()
        phs = [sub.placeholder(f"sub_in_{i}") for i in range(n_in)]
        outs = build(sub, phs)
        outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
        out_names = tuple(o.name() for o in outs)
        payload = {"n_in": n_in, "outputs": list(out_names),
                   "graph": sub._graph_payload()}
        return self._stage_from(sub, out_names), len(out_names), payload

    @staticmethod
    def _stage_from(sub: "SameDiff", out_names) -> Any:
        subfn = sub._build_fn(tuple(out_names))
        var_vals = sub._var_values()

        def staged(*args):
            res = subfn({f"sub_in_{i}": a for i, a in enumerate(args)},
                        var_vals, 0)
            return [res[n] for n in out_names]
        return staged

    def _graph_payload(self, include_arrays: bool = True) -> Dict:
        """JSON-able description of this graph.  With ``include_arrays``
        values are inlined (control-flow sub-graph regions — small loop
        constants); ``save`` passes False and writes arrays.npz instead.
        Guard (applies recursively through nested regions): a callable
        attr is only serializable when it is a known control-flow fn key
        whose paired ``_sub_*`` region is present."""
        for n in self._ops:
            pairs = dict(self._CF_SUBS.get(n.op, ()))
            for k, a in n.attrs.items():
                if callable(a) and (k not in pairs
                                    or pairs[k] not in n.attrs):
                    raise ValueError(
                        f"cannot serialize op '{n.name}' ({n.op}): attr "
                        f"{k!r} is a compile-time closure with no "
                        "serialized sub-graph region")
        payload = {
            "variables": [
                {"name": v.name(), "type": v.variableType,
                 "shape": list(v.shape) if v.shape else None,
                 "dtype": (np.dtype(v.dtype).name
                           if v.dtype is not None else None)}
                for v in self._vars.values()],
            "ops": [{"op": n.op, "name": n.name, "inputs": n.inputs,
                     "outputs": n.outputs,
                     "attrs": {k: a for k, a in n.attrs.items()
                               if not callable(a)}}
                    for n in self._ops],
            "lossVariables": list(self._loss_vars),
        }
        if include_arrays:
            payload["arrays"] = {n: {"dtype": str(np.asarray(a).dtype),
                                     "data": np.asarray(a).tolist()}
                                 for n, a in self._arrays.items()}
        return payload

    def _apply_graph_payload(self, g: Dict) -> None:
        """Reconstruct variables/ops/loss markers from a payload dict
        (shared by ``load`` and sub-graph region restaging)."""
        for v in g["variables"]:
            dt = np.dtype(v["dtype"]) if v.get("dtype") else None
            self._register(v["name"], v["type"], v.get("shape"), dt)
        for o in g["ops"]:
            node = _OpNode(o["op"], o["name"], o["inputs"], o["outputs"],
                           o["attrs"])
            self._ops.append(node)
            for i, out in enumerate(node.outputs):
                self._producer[out] = (node, i)
        self._loss_vars = g.get("lossVariables", [])

    #: control-flow ops: (callable attr -> serialized sub-graph attr)
    _CF_SUBS = {
        "while_loop": (("cond_fn", "_sub_cond"), ("body_fn", "_sub_body")),
        "if_cond": (("cond_fn", "_sub_cond"), ("true_fn", "_sub_true"),
                    ("false_fn", "_sub_false")),
        "for_loop": (("body_fn", "_sub_body"),),
    }

    @staticmethod
    def _restage_payload(payload: Dict) -> Any:
        """Rebuild a staged sub-graph closure from its serialized form
        (recursively — nested control flow restages its own regions)."""
        g = payload["graph"]
        sub = SameDiff()
        sub._apply_graph_payload(g)
        for n, spec in g["arrays"].items():
            sub._arrays[n] = jnp.asarray(
                np.asarray(spec["data"], dtype=np.dtype(spec["dtype"])))
        sub._restage_controlflow()
        return SameDiff._stage_from(sub, tuple(payload["outputs"]))

    def _restage_controlflow(self) -> None:
        """After load: re-create the compile-time closures of every
        control-flow op from their serialized sub-graph regions."""
        for n in self._ops:
            for fn_key, sub_key in self._CF_SUBS.get(n.op, ()):
                if not callable(n.attrs.get(fn_key)):
                    n.attrs[fn_key] = self._restage_payload(
                        n.attrs[sub_key])

    def whileLoop(self, loopVars: Sequence[SDVariable], cond, body,
                  name: str = None):
        """TF-style while loop (reference: SameDiff.whileLoop with
        Enter/Exit/Switch/Merge lowering — here a single
        ``lax.while_loop``).

        ``cond(sd, vars) -> scalar-bool SDVariable``;
        ``body(sd, vars) -> list of SDVariable`` (same arity as loopVars).
        Forward-only: XLA's while is not reverse-differentiable — matching
        the reference, whose imported TF loops don't train either.  Returns
        the final loop variables.
        """
        n = len(loopVars)
        cond_fn, n_c, cond_sub = self._stage_subgraph(n, cond)
        if n_c != 1:
            raise ValueError("cond must return exactly one scalar")
        body_fn, n_b, body_sub = self._stage_subgraph(n, body)
        if n_b != n:
            raise ValueError(f"body returns {n_b} vars, expected {n}")
        out = self._op("while_loop", list(loopVars),
                       {"cond_fn": cond_fn, "body_fn": body_fn, "n": n,
                        "_sub_cond": cond_sub, "_sub_body": body_sub},
                       n_out=n, name=name or "while")
        return out if isinstance(out, list) else [out]

    def ifCond(self, inputs: Sequence[SDVariable], cond, trueBody, falseBody,
               name: str = None):
        """TF-style conditional (reference: SameDiff.ifCond / Switch+Merge —
        here one ``lax.cond``, differentiable).  cond/trueBody/falseBody are
        ``f(sd, vars)`` lambdas; the two branches must return the same
        number (and shapes) of outputs."""
        n = len(inputs)
        cond_fn, n_c, cond_sub = self._stage_subgraph(n, cond)
        if n_c != 1:
            raise ValueError("cond must return exactly one scalar")
        t_fn, n_t, t_sub = self._stage_subgraph(n, trueBody)
        f_fn, n_f, f_sub = self._stage_subgraph(n, falseBody)
        if n_t != n_f:
            raise ValueError(f"branches return {n_t} vs {n_f} outputs")
        out = self._op("if_cond", list(inputs),
                       {"cond_fn": cond_fn, "true_fn": t_fn,
                        "false_fn": f_fn, "n_out": n_t,
                        "_sub_cond": cond_sub, "_sub_true": t_sub,
                        "_sub_false": f_sub},
                       n_out=n_t, name=name or "cond")
        return out if isinstance(out, list) else [out]

    def forLoop(self, nIterations: int, loopVars: Sequence[SDVariable], body,
                name: str = None):
        """Fixed-trip-count loop via ``lax.scan`` — DIFFERENTIABLE (the
        TPU-native recurrence primitive; use instead of whileLoop when the
        trip count is static and gradients must flow)."""
        n = len(loopVars)
        body_fn, n_b, body_sub = self._stage_subgraph(n, body)
        if n_b != n:
            raise ValueError(f"body returns {n_b} vars, expected {n}")
        out = self._op("for_loop", list(loopVars),
                       {"body_fn": body_fn, "n": n,
                        "iterations": int(nIterations),
                        "_sub_body": body_sub},
                       n_out=n, name=name or "for")
        return out if isinstance(out, list) else [out]

    # ---------------- shape / array ops (reference: SDBaseOps on the
    # SameDiff class itself — sd.concat/gather/tile/...) ----------------
    def concat(self, dimension: int, *vars, name=None):
        return self._op("concat", list(vars), {"dimension": dimension},
                        name=name)

    def stack(self, axis: int, *vars, name=None):
        return self._op("stack", list(vars), {"axis": axis}, name=name)

    def unstack(self, var, axis: int, num: int, name=None):
        return self._op("unstack", [var], {"axis": axis, "num": num},
                        n_out=num, name=name)

    def gather(self, x, indices, axis=0, name=None):
        ix = indices if isinstance(indices, SDVariable) \
            else self.constant(np.asarray(indices))
        return self._op("gather", [x, ix], {"axis": axis}, name=name)

    def tile(self, x, reps, name=None):
        return self._op("tile", [x], {"reps": tuple(reps)}, name=name)

    def reverse(self, x, *dims, name=None):
        return self._op("reverse", [x], {"dims": dims or (0,)}, name=name)

    def slice(self, x, begin, size, name=None):
        return self._op("slice", [x], {"begin": tuple(begin),
                                       "size": tuple(size)}, name=name)

    def stridedSlice(self, x, begin, end, strides=None, name=None):
        return self._op("stridedSlice", [x],
                        {"begin": tuple(begin), "end": tuple(end),
                         "strides": tuple(strides) if strides else None},
                        name=name)

    def oneHot(self, indices, depth, on=1.0, off=0.0, axis=-1, name=None):
        return self._op("oneHot", [indices],
                        {"depth": depth, "on": on, "off": off, "axis": axis},
                        name=name)

    def where(self, cond, x, y, name=None):
        return self._op("where", [cond, x, y], name=name)

    def zerosLike(self, x, name=None):
        return self._op("zerosLike", [x], name=name)

    def onesLike(self, x, name=None):
        return self._op("onesLike", [x], name=name)

    def invokeGraphOn(self, other: "SameDiff"):
        """Copy this graph's structure into ``other`` (used by subgraphs)."""
        for n, v in self._vars.items():
            other._vars[n] = SDVariable(other, n, v.variableType, v.shape,
                                        v.dtype)
        other._arrays.update(self._arrays)
        for node in self._ops:
            cp = _OpNode(node.op, node.name, list(node.inputs),
                         list(node.outputs), dict(node.attrs))
            other._ops.append(cp)
            for i, o in enumerate(cp.outputs):
                other._producer[o] = (cp, i)

    # ---------------- staging: graph -> pure function ----------------
    def _needed_nodes(self, out_names: Sequence[str]) -> List[_OpNode]:
        """Reverse-reachability prune + topological order."""
        needed: List[_OpNode] = []
        seen = set()

        def visit(name):
            if name in seen:
                return
            seen.add(name)
            prod = self._producer.get(name)
            if prod is None:
                return
            node, _ = prod
            for i in node.inputs:
                visit(i)
            if node not in needed:
                needed.append(node)

        for n in out_names:
            visit(n)
        return needed

    def _build_fn(self, out_names: Tuple[str, ...], training: bool = False,
                  compute_dtype=None):
        """Stage the graph into a pure fn(placeholders, variables, it) -> outs.

        ``it`` is the iteration counter: train-time RNG ops (dropout) fold it
        into their key for a fresh mask per step; at inference they are
        identity (matching ND4J DropOutInverted train/test semantics).
        """
        nodes = self._needed_nodes(out_names)
        compiled = []
        for node in nodes:
            if node.op in RNG_TRAIN_OPS and not training:
                compiled.append((node, None))  # identity at inference
            else:
                compiled.append((node, OP_IMPLS[node.op](**node.attrs)))
        consts = {n: a for n, a in self._arrays.items()
                  if self._vars[n].variableType == VariableType.CONSTANT}
        if compute_dtype is not None:
            # graph constants must follow the compute dtype, or one strong
            # f32 constant re-promotes its whole bf16 subgraph back to f32
            consts = {n: (a.astype(compute_dtype) if hasattr(a, "dtype")
                          and a.dtype == jnp.float32 else a)
                      for n, a in consts.items()}

        def fn(placeholders: Dict[str, jnp.ndarray],
               variables: Dict[str, jnp.ndarray],
               it=0):
            env = dict(consts)
            env.update(placeholders)
            env.update(variables)
            for node, impl in compiled:
                if impl is None:
                    env[node.outputs[0]] = env[node.inputs[0]]
                    continue
                args = [env[i] for i in node.inputs]
                if node.op in RNG_TRAIN_OPS:
                    res = impl(*args, it)
                else:
                    res = impl(*args)
                if isinstance(res, (tuple, list)):
                    for nm, r in zip(node.outputs, res):
                        env[nm] = r
                else:
                    env[node.outputs[0]] = res
            return {n: env[n] for n in out_names}
        return fn

    def _var_values(self) -> Dict[str, jnp.ndarray]:
        return {n: a for n, a in self._arrays.items()
                if self._vars[n].variableType == VariableType.VARIABLE}

    # ---------------- execution ----------------
    def output(self, placeholders: Dict[str, Any], *outputs) -> Dict[str, NDArray]:
        """Inference: compile once per (outputs, placeholder-shape) signature.

        Replaces InferenceSession's op-by-op dispatch (SURVEY §3.3) with ONE
        XLA executable.
        """
        out_names = tuple(o.name() if isinstance(o, SDVariable) else o
                          for o in outputs)
        if not out_names:
            out_names = tuple(self._loss_vars)
        ph = {k: jnp.asarray(_to_np(v)) for k, v in (placeholders or {}).items()}
        sig = (out_names, tuple(sorted((k, v.shape, str(v.dtype))
                                       for k, v in ph.items())))
        if sig not in self._fn_cache:
            self._fn_cache[sig] = jax.jit(self._build_fn(out_names))
        res = self._fn_cache[sig](ph, self._var_values())
        return {k: NDArray(v) for k, v in res.items()}

    # aliases matching the reference API surface
    exec = output
    batchOutput = output

    def outputSingle(self, placeholders, output) -> NDArray:
        name = output.name() if isinstance(output, SDVariable) else output
        return self.output(placeholders, name)[name]

    def calculateGradients(self, placeholders: Dict[str, Any],
                           *wrt) -> Dict[str, NDArray]:
        """d(sum of loss variables)/d(wrt) — ``jax.grad`` replaces the
        reference's createGradFunction grad-graph (SURVEY §3.3)."""
        if not self._loss_vars:
            raise ValueError("No loss variables set (markAsLoss / "
                             "setLossVariables)")
        wrt_names = [w.name() if isinstance(w, SDVariable) else w for w in wrt]
        if not wrt_names:
            wrt_names = [n for n, v in self._vars.items()
                         if v.variableType == VariableType.VARIABLE]
        ph = {k: jnp.asarray(_to_np(v)) for k, v in placeholders.items()}
        sig = ("__grad__", tuple(self._loss_vars),
               tuple(sorted((k, v.shape, str(v.dtype)) for k, v in ph.items())))
        if sig not in self._fn_cache:
            fn = self._build_fn(tuple(self._loss_vars), training=True)

            def loss_fn(variables, ph_):
                outs = fn(ph_, variables)
                return sum(jnp.sum(v) for v in outs.values())

            self._fn_cache[sig] = jax.jit(jax.grad(loss_fn))
        grads = self._fn_cache[sig](self._var_values(), ph)
        self._last_grads = dict(grads)
        return {n: NDArray(grads[n]) for n in wrt_names if n in grads}

    grad = calculateGradients

    # ---------------- training ----------------
    def setTrainingConfig(self, cfg: TrainingConfig):
        if (self._training_config is not None
                and type(cfg.updater) is not type(self._training_config.updater)):
            self._opt_state = None  # updater changed: old state is meaningless
        self._training_config = cfg
        self._train_step = None

    def stepCostAnalysis(self, ds) -> Dict[str, float]:
        """XLA cost analysis of the exact compiled train step for ``ds``
        (a DataSet/MultiDataSet): ``{"flops": ..., "bytes": ...}`` — the
        basis for MFU/roofline reporting.  Requires setTrainingConfig;
        compiles the step if needed."""
        if self._training_config is None:
            raise ValueError("setTrainingConfig first")
        if self._train_step is None:
            self._make_train_step()
        variables = self._var_values()
        opt = dict(self._opt_state or {})
        for n, v in variables.items():
            if n not in opt:
                opt[n] = self._training_config.updater.init(v)
        low = self._train_step.lower(
            variables, opt, self._bind(ds, self._training_config),
            jnp.asarray(self.iterationCount, jnp.int32))
        # the compiled executable's analysis: Lowered.cost_analysis() is
        # not implemented for a PJRT plug-in backend, which the TPU is.
        # The jit call cache is not shared with .compile(), so this
        # compiles again (a hit where the persistent cache is on).
        ca = low.compile().cost_analysis()
        return {"flops": float(ca["flops"]),
                "bytes": float(ca["bytes accessed"])}

    def _make_train_step(self):
        cfg = self._training_config
        fn = self._build_fn(tuple(self._loss_vars), training=True)
        updater = cfg.updater
        ph_names = cfg.dataSetFeatureMapping + cfg.dataSetLabelMapping
        sign = 1.0 if cfg.minimize else -1.0
        cdt = jnp.bfloat16 if str(cfg.dataType).upper() in (
            "BFLOAT16", "HALF", "FLOAT16") else jnp.float32
        if cdt != jnp.float32:
            fn = self._build_fn(tuple(self._loss_vars), training=True,
                                compute_dtype=cdt)

        def cast_compute(tree):
            if cdt == jnp.float32:
                return tree
            return {k: (v.astype(cdt) if hasattr(v, "dtype")
                        and v.dtype == jnp.float32 else v)
                    for k, v in tree.items()}

        def loss_fn(variables, ph, it):
            outs = fn(cast_compute(ph), cast_compute(variables), it)
            # loss reductions in f32 under bf16 compute
            loss = sum(jnp.sum(v.astype(jnp.float32)
                               if hasattr(v, "dtype") and v.dtype == cdt
                               else v) for v in outs.values())
            if cfg.l2:
                # 0.5*l2*sum(w^2) — matches _reg_penalty / DL4J convention
                loss = loss + 0.5 * cfg.l2 * sum(
                    jnp.sum(v * v) for v in variables.values())
            if cfg.l1:
                loss = loss + cfg.l1 * sum(
                    jnp.sum(jnp.abs(v)) for v in variables.values())
            return loss

        def step(variables, opt_state, ph, it):
            loss, grads = jax.value_and_grad(loss_fn)(variables, ph, it)
            lr = updater.currentLr(it, 0)
            new_vars, new_state = {}, {}
            for n, g in grads.items():
                upd, st = updater.apply(sign * g, opt_state[n], lr, it,
                                        param=variables[n])
                new_vars[n] = variables[n] - upd
                new_state[n] = st
            return new_vars, new_state, loss

        # NO buffer donation here (unlike MultiLayerNetwork's fused step):
        # donated outputs can carry non-default layouts, so the NEXT fit()
        # call — whose inputs are those outputs — misses the jit cache and
        # recompiles with layout-conversion copies (observed: a BERT-base
        # second fit recompiling for minutes, then OOMing on copy temps).
        # Default layouts keep every fit() call on one cached executable.
        self._train_step = jax.jit(step)
        self._ph_names = ph_names

    def fit(self, data=None, epochs: int = 1) -> "History":
        """Train (reference: SameDiff.fit / TrainingSession, SURVEY §3.3).

        ``data`` is a DataSet, MultiDataSet, or iterator thereof; features
        and labels bind to placeholders via the TrainingConfig mappings.
        One jitted step = fwd + bwd + updater (north star).
        """
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
        if self._training_config is None:
            raise ValueError("setTrainingConfig first")
        cfg = self._training_config
        if self._train_step is None:
            self._make_train_step()
        variables = self._var_values()
        if self._opt_state is None:
            self._opt_state = {}
        for n, v in variables.items():
            if n not in self._opt_state:  # extend for vars added after a fit
                self._opt_state[n] = cfg.updater.init(v)
        from deeplearning4j_tpu.autodiff.listeners import At, Loss
        from deeplearning4j_tpu.optimize.listeners import notifyListeners
        losses, curve = [], []
        for ep in range(int(epochs)):
            at = At(epoch=ep, iteration=self.iterationCount)
            notifyListeners(self._listeners, "epochStart", self, at)
            if isinstance(data, (DataSet, MultiDataSet)):
                batches = [data]
            else:
                if hasattr(data, "reset"):
                    data.reset()
                batches = data
            for ds in batches:
                at = At(epoch=ep, iteration=self.iterationCount)
                notifyListeners(self._listeners, "iterationStart", self,
                                at, ds)
                ph = self._bind(ds, cfg)
                variables, self._opt_state, loss = self._train_step(
                    variables, self._opt_state, ph,
                    jnp.asarray(self.iterationCount, jnp.int32))
                self.iterationCount += 1
                # Device scalar, fetched lazily — a float() here would block
                # dispatch on a host round-trip every step.  With listeners
                # attached the host sync is paid anyway (the listener
                # contract is a Python float), so convert only then.
                losses.append(loss)
                if self._listeners:
                    # float() only with listeners attached — see comment
                    # above: listener-free fits keep the loss async
                    notifyListeners(
                        self._listeners, "iterationDone", self, at, ds,
                        Loss(["loss"], [float(losses[-1])]))
            if self._listeners:
                curve = _fetch_curve(losses)
                notifyListeners(self._listeners, "epochEnd", self,
                                At(epoch=ep, iteration=self.iterationCount),
                                loss_curve=curve)
        self._arrays.update(variables)
        # Reuse the last epochEnd fetch when listeners ran (nothing was
        # appended after it); otherwise one stacked transfer.
        if self._listeners and len(curve) == len(losses):
            losses = curve
        else:
            losses = _fetch_curve(losses)
        return History(losses)

    def _bind(self, ds, cfg) -> Dict[str, jnp.ndarray]:
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            feats = [jnp.asarray(_to_np(f)) for f in ds.features]
            labs = [jnp.asarray(_to_np(l)) for l in ds.labels]
        else:
            feats = [jnp.asarray(_to_np(ds.features))]
            labs = [jnp.asarray(_to_np(ds.labels))]
        ph = {}
        for n, a in zip(cfg.dataSetFeatureMapping, feats):
            ph[n] = a
        for n, a in zip(cfg.dataSetLabelMapping, labs):
            ph[n] = a
        return ph

    def evaluate(self, iterator, outputVariable, evaluation=None):
        """Reference: SameDiff.evaluate(DataSetIterator, outputVariable,
        IEvaluation) — features bind via the TrainingConfig feature mapping,
        labels come from each DataSet, predictions from ``outputVariable``."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        if self._training_config is None:
            raise ValueError("setTrainingConfig first (feature mappings)")
        cfg = self._training_config
        ev = evaluation or Evaluation()
        name = outputVariable.name() if isinstance(outputVariable,
                                                   SDVariable) \
            else outputVariable
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            # one binding path: _bind handles DataSet vs MultiDataSet (the
            # bound label placeholders are simply unused by the output fetch)
            ph = self._bind(ds, cfg)
            out = self.outputSingle(
                {k: v for k, v in ph.items()
                 if k in cfg.dataSetFeatureMapping}, name)
            labels = ds.labels[0] if isinstance(ds.labels, list) else ds.labels
            lmask = getattr(ds, "labelsMasks", None)   # MultiDataSet plural
            if isinstance(lmask, list):
                lmask = lmask[0] if lmask else None
            if lmask is None:
                lmask = getattr(ds, "labelsMask", None)
            ev.eval(_to_np(labels), out.numpy(),
                    _to_np(lmask) if lmask is not None else None)
        return ev

    # ---------------- listeners (reference: BaseListener SPI) ----------
    def setListeners(self, *listeners) -> None:
        if len(listeners) == 1 and isinstance(listeners[0], (list, tuple)):
            listeners = tuple(listeners[0])
        self._listeners = list(listeners)

    def addListeners(self, *listeners) -> None:
        self._listeners.extend(listeners)

    def execDebug(self, placeholders: Dict[str, Any], *outputs):
        """Op-by-op UNCOMPILED execution firing preOpExecution/opExecution
        on every listener — the observability mode the reference gets for
        free from per-op dispatch (and pays for in speed).  Returns the same
        dict as :meth:`output`."""
        from deeplearning4j_tpu.autodiff.listeners import At
        out_names = tuple(o.name() if isinstance(o, SDVariable) else o
                          for o in outputs) or tuple(self._loss_vars)
        nodes = self._needed_nodes(out_names)
        env = {n: a for n, a in self._arrays.items()}
        env.update({k: jnp.asarray(_to_np(v))
                    for k, v in placeholders.items()})
        at = At(iteration=self.iterationCount)
        for node in nodes:
            for l in self._listeners:
                l.preOpExecution(self, at, node)
            args = [env[i] for i in node.inputs]
            if node.op in RNG_TRAIN_OPS:
                # inference semantics, like output(): dropout is identity
                res = args[0]
            else:
                res = OP_IMPLS[node.op](**node.attrs)(*args)
            res_t = res if isinstance(res, (tuple, list)) else (res,)
            for nm, r in zip(node.outputs, res_t):
                env[nm] = r
            for l in self._listeners:
                l.opExecution(self, at, node, list(res_t))
        for l in self._listeners:
            hook = getattr(l, "execDebugPassDone", None)
            if hook is not None:
                hook(self, at)
        return {n: NDArray(env[n]) for n in out_names}

    # ---------------- serde ----------------
    def save(self, path: str, saveUpdaterState: bool = False):
        """Zip with graph.json + npz arrays (reference: SameDiff.save →
        FlatBuffers, libnd4j graph/scheme/*.fbs; same content, JSON+npz
        container).  Control-flow ops serialize their sub-graph regions
        recursively (``_sub_*`` attrs — the FlatBuffers scheme stored
        nested graphs the same way); the staged closures are dropped and
        rebuilt on load.  An op with a callable attr but NO paired
        serialized region (hand-registered, not framework-built) refuses
        — the guard lives in ``_graph_payload``."""
        graph = self._graph_payload(include_arrays=False)
        buf = io.BytesIO()
        np.savez(buf, **{n: np.asarray(a) for n, a in self._arrays.items()})
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("graph.json", json.dumps(graph, default=str))
            z.writestr("arrays.npz", buf.getvalue())
            if saveUpdaterState and self._opt_state is not None:
                sbuf = io.BytesIO()
                flat = {}
                for n, st in self._opt_state.items():
                    for k, a in st.items():
                        if isinstance(a, jnp.ndarray):
                            flat[f"{n}/{k}"] = np.asarray(a)
                np.savez(sbuf, **flat)
                z.writestr("updater.npz", sbuf.getvalue())

    @staticmethod
    def load(path: str, loadUpdaterState: bool = False) -> "SameDiff":
        sd = SameDiff()
        with zipfile.ZipFile(path) as z:
            graph = json.loads(z.read("graph.json"))
            arrays = np.load(io.BytesIO(z.read("arrays.npz")))
            sd._apply_graph_payload(graph)
            for n in arrays.files:
                sd._arrays[n] = jnp.asarray(arrays[n])
            sd._restage_controlflow()
            if loadUpdaterState and "updater.npz" in z.namelist():
                st = np.load(io.BytesIO(z.read("updater.npz")))
                opt: Dict[str, Dict] = {}
                for key in st.files:
                    n, k = key.rsplit("/", 1)
                    opt.setdefault(n, {})[k] = jnp.asarray(st[key])
                sd._opt_state = opt
        return sd

    def summary(self) -> str:
        lines = [f"SameDiff: {len(self._vars)} variables, "
                 f"{len(self._ops)} ops"]
        for v in self._vars.values():
            if v.variableType != VariableType.ARRAY:
                lines.append(f"  {v.variableType:<12} {v.name():<24} "
                             f"{v.shape}")
        for n in self._ops:
            lines.append(f"  OP {n.op:<24} {n.inputs} -> {n.outputs}")
        return "\n".join(lines)


class History:
    """Reference: org/nd4j/autodiff/listeners/records/History.java."""

    def __init__(self, losses: List[float]):
        self._losses = losses

    def lossCurve(self) -> List[float]:
        return list(self._losses)

    def finalTrainingLoss(self) -> float:
        return self._losses[-1] if self._losses else float("nan")


# Extended declarable-op families (segment/scatter/reduce3/summarystats/
# image/linalg/rnn) register themselves into OP_IMPLS on import; kept in a
# sibling module so this file stays the core graph machinery.
from deeplearning4j_tpu.autodiff import ops_ext  # noqa: E402,F401  isort:skip
from deeplearning4j_tpu.autodiff import ops_ext2  # noqa: E402,F401  isort:skip
from deeplearning4j_tpu.autodiff import ops_ext3  # noqa: E402,F401  isort:skip
from deeplearning4j_tpu.autodiff import ops_ext4  # noqa: E402,F401  isort:skip
from deeplearning4j_tpu.autodiff import ops_ext5  # noqa: E402,F401  isort:skip
