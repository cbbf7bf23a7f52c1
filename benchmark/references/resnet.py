"""Plain reference for the bottleneck ResNet family (He et al.,
arXiv:1512.03385, Table 1): forward with BatchNorm in training mode,
softmax cross-entropy, its gradients, and Nesterov-momentum steps, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.

It imports nothing of the program and takes nothing the program made: the
weights and batches are made here from the seed, and the family's builder
(``configs/resnet.py``) copies them *into* the program.

Departures from the paper, each the program's and followed here so that
the two compute the same function: the stride of a stage's first block sits
on its first 1x1 convolution (the paper's v1 placement, not the later
"v1.5" on the 3x3); convolutions have no bias; ``SAME`` padding as XLA
defines it (the 7x7/2 stem pads 2 before and 3 after); BatchNorm uses the
biased batch variance with eps 1e-5; the loss is the mean over the batch.
The program also clips probabilities to [1e-7, 1 - 1e-7] before the
logarithm; at seeded weights no probability comes near either end, so the
reference uses the plain log-softmax and the clip would show as a gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
_DN = ("NCHW", "OIHW", "NCHW")
#: float8 e4m3's largest finite value, the per-tensor scale of the control
_F8_MAX = 448.0


# --------------------------------------------------------------------------
# weights and batches from the seed
# --------------------------------------------------------------------------

def _unit_shapes(config: dict):
    """Yield ``(path, out_ch, in_ch, k)`` of every conv+BN unit in forward
    order; ``path`` is a tuple into the weights tree."""
    yield ("stem",), 64, config["in_channels"], 7
    inp = 64
    for si, (width, blocks, _stride) in enumerate(config["stages"]):
        for bi in range(blocks):
            base = ("stages", si, bi)
            yield base + ("a",), width, inp, 1
            yield base + ("b",), width, width, 3
            yield base + ("c",), width * 4, width, 1
            if bi == 0:
                yield base + ("sc",), width * 4, inp, 1
            inp = width * 4


def make_weights(config: dict, key):
    """Seeded weights in the reference's own tree, made on the device in
    one jitted call: He-normal convolutions (std sqrt(2 / fan_in)),
    gamma 1, beta 0, and a He-normal classifier with zero bias."""
    units = list(_unit_shapes(config))
    feat = config["stages"][-1][0] * 4
    classes = config["num_classes"]

    @jax.jit
    def build(key):
        tree = {"stem": None,
                "stages": [[{} for _ in range(b)]
                           for _w, b, _s in config["stages"]]}
        for i, (path, o, c, k) in enumerate(units):
            std = (2.0 / (c * k * k)) ** 0.5
            unit = {"conv": std * jax.random.normal(
                        jax.random.fold_in(key, i), (o, c, k, k),
                        jnp.float32),
                    "gamma": jnp.ones((o,), jnp.float32),
                    "beta": jnp.zeros((o,), jnp.float32)}
            if path == ("stem",):
                tree["stem"] = unit
            else:
                _s, si, bi, which = path
                tree["stages"][si][bi][which] = unit
        tree["fc"] = {
            "W": (2.0 / feat) ** 0.5 * jax.random.normal(
                jax.random.fold_in(key, len(units)), (feat, classes),
                jnp.float32),
            "b": jnp.zeros((classes,), jnp.float32)}
        return tree
    return build(key)


def make_batches(config: dict, key, n: int, batch: int):
    """``n`` batches whose rows all differ: standard-normal images
    ``(batch, C, H, W)`` float32 and one-hot float32 labels, made on the
    device."""
    c, h, w = config["in_channels"], config["image"], config["image"]
    classes = config["num_classes"]

    @jax.jit
    def build(key):
        out = []
        for i in range(n):
            kx, ky = jax.random.split(jax.random.fold_in(key, 1000 + i))
            x = jax.random.normal(kx, (batch, c, h, w), jnp.float32)
            y = jax.nn.one_hot(jax.random.randint(ky, (batch,), 0, classes),
                               classes, dtype=jnp.float32)
            out.append((x, y))
        return out
    return build(key)


# --------------------------------------------------------------------------
# forward, loss, steps
# --------------------------------------------------------------------------

def _fake_quant(x):
    """The control's rounding: scale the tensor into float8 e4m3's range,
    round, scale back.  The gradient passes straight through, as in fp8
    training recipes."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / _F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + lax.stop_gradient(q - x)


def _conv_bn(unit, x, stride: int, relu: bool, quant: bool):
    """Convolution, BatchNorm on the batch's own statistics, ReLU or not.
    Returns the activations and the batch ``(mean, variance)``."""
    w = unit["conv"]
    if quant:
        x, w = _fake_quant(x), _fake_quant(w)
    y = lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                 dimension_numbers=_DN,
                                 precision=lax.Precision.HIGHEST)
    mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean((y - mean) ** 2, axis=(0, 2, 3), keepdims=True)
    y = (y - mean) * lax.rsqrt(var + EPS)
    y = y * unit["gamma"].reshape(1, -1, 1, 1) \
        + unit["beta"].reshape(1, -1, 1, 1)
    stats = (mean.reshape(-1), var.reshape(-1))
    return (jnp.maximum(y, 0.0) if relu else y), stats


def _block(block, x, stride: int, quant: bool):
    stats = {}
    y, stats["a"] = _conv_bn(block["a"], x, stride, True, quant)
    y, stats["b"] = _conv_bn(block["b"], y, 1, True, quant)
    y, stats["c"] = _conv_bn(block["c"], y, 1, False, quant)
    sc = x
    if "sc" in block:
        sc, stats["sc"] = _conv_bn(block["sc"], x, stride, False, quant)
    return jnp.maximum(y + sc, 0.0), stats


def logits(config: dict, weights, x, quant: bool = False):
    """``(logits, batch statistics)``: the statistics are the
    ``(mean, variance)`` of every BatchNorm, in the weights' structure."""
    y, stem = _conv_bn(weights["stem"], x, 2, True, quant)
    stats = {"stem": stem, "stages": []}
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          "SAME")
    for (_w, _b, stride), blocks in zip(config["stages"], weights["stages"]):
        stats["stages"].append([])
        for bi, block in enumerate(blocks):
            # activations of a block are recomputed in the backward pass,
            # so that a float32 batch of the cell's size fits beside
            # nothing else on one chip
            y, st = jax.checkpoint(functools.partial(
                _block, stride=stride if bi == 0 else 1, quant=quant))(
                    block, y)
            stats["stages"][-1].append(st)
    feat = jnp.mean(y, axis=(2, 3))
    w = weights["fc"]["W"]
    if quant:
        feat, w = _fake_quant(feat), _fake_quant(w)
    return jnp.matmul(feat, w, precision=lax.Precision.HIGHEST) \
        + weights["fc"]["b"], stats


def loss(config: dict, weights, x, y, quant: bool = False):
    """``(mean softmax cross-entropy, batch statistics)``."""
    out, stats = logits(config, weights, x, quant)
    logp = jax.nn.log_softmax(out, axis=-1)
    return -jnp.mean(jnp.sum(y * logp, axis=-1)), stats


def leaf_paths(tree) -> list:
    """``a/b/c`` names of the leaves, in ``jax.tree`` order."""
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _leaf in jax.tree_util.tree_leaves_with_path(tree)]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(tree)])


def train_steps(config: dict, weights, batches, quant: bool = False) -> dict:
    """Follow the first ``len(batches)`` Nesterov steps from ``weights``
    (v <- mu v - lr g; w <- w - mu v_prev + (1 + mu) v, the form of
    Sutskever et al. 2013 that DL4J's ``NesterovsUpdater`` applies).

    Returns the loss of each step, the norm of every leaf of the first
    gradient, the first gradient of the classifier's weights itself, and
    the norm of every leaf's change after the last step.  ``quant``
    computes the control: every convolution and the classifier take
    float8-rounded inputs and weights.
    """
    lr, mu = config["learning_rate"], config["momentum"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(w, v, x, y):
        (val, stats), g = jax.value_and_grad(
            lambda w: loss(config, w, x, y, quant), has_aux=True)(w)
        v_new = jax.tree.map(lambda v, g: mu * v - lr * g, v, g)
        w_new = jax.tree.map(lambda w, vp, vn: w - mu * vp + (1 + mu) * vn,
                             w, v, v_new)
        return w_new, v_new, val, leaf_norms(g), head_leaf(g), stats

    delta = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract,
                                                         a, b)))
    w = jax.tree.map(jnp.copy, weights)
    v = jax.tree.map(jnp.zeros_like, weights)
    losses, grad0, head0, stats0 = [], None, None, None
    for i, (x, y) in enumerate(batches):
        w, v, val, gn, head, stats = step(w, v, x, y)
        losses.append(float(val))
        if i == 0:
            grad0, head0, stats0 = [float(t) for t in gn], head, stats
    return {"losses": losses, "grad_norms": grad0, "head_grad": head0,
            "batch_stats": stats0,
            "delta_norms": [float(t) for t in delta(w, weights)],
            "leaves": leaf_paths(weights)}


def head_leaf(tree):
    """The classifier's weights: the one leaf whose gradient depends on
    the forward pass alone (printed, see :func:`compare`)."""
    return tree["fc"]["W"]


# --------------------------------------------------------------------------
# the comparison that decides ``correct``
# --------------------------------------------------------------------------

def batch_var_err(got, want) -> float:
    """Over the BatchNorms, the median of the relative L2 error (over the
    channels) of the first step's batch variances."""
    is_pair = lambda t: isinstance(t, tuple)
    errs = sorted(
        float(jnp.linalg.norm(g[1] - w[1]) / jnp.linalg.norm(w[1]))
        for g, w in zip(jax.tree.leaves(got, is_leaf=is_pair),
                        jax.tree.leaves(want, is_leaf=is_pair)))
    return errs[len(errs) // 2]


def leaf_gaps(got, want) -> list:
    """``|got - want|`` of every leaf's norm, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    srt = sorted(want)
    median = srt[len(srt) // 2]
    return [abs(g - w) / max(w, median) for g, w in zip(got, want)]


def compare(got: dict, want: dict) -> dict:
    """The numbers compared, each with a limit of its own in the
    configuration file.

    A ReLU network's forward pass and gradient are discontinuous in their
    activations: a rounding that flips a gate changes what flows through
    it, so element-wise errors grow like the square root of (rounding x
    depth) and saturate.  At 50 layers bfloat16 already moves single
    leaves' gradient norms by 15-40% and the classifier's gradient by 6-9%
    against float32, and float8 hardly more (measured, PERF.md), so no
    element-wise number tells one precision from the next.  Statistics
    over the batch are smooth in the rounding.  So:

    - ``batch_var_err`` is the number that holds the precision: the
      median over the 53 BatchNorms of the relative error of the first
      step's batch variances, as the program's running statistics hold
      them after one step;
    - ``loss0_gap`` (the loss at the seeded weights) and ``loss_gap`` (the
      later steps' losses) are held against a wrong forward pass or
      update and, weakly, a part of the batch left out;
    - ``grad_norm_gap`` and ``delta_norm_gap`` are the median over the
      leaves of the gap between the program's norm and the reference's
      (first gradient as the optimizer got it; parameters' change after
      the steps), held against a gradient that was not exchanged between
      chips and a step that returns its state unchanged, each of which
      moves every leaf.  The worst leaf, which swings by its nature, and
      the classifier's gradient error are printed beside them.
    """
    rel = [abs(a - b) / abs(b) for a, b in zip(got["losses"], want["losses"])]
    grad = sorted(leaf_gaps(got["grad_norms"], want["grad_norms"]))
    delta = sorted(leaf_gaps(got["delta_norms"], want["delta_norms"]))
    head = jnp.linalg.norm(got["head_grad"] - want["head_grad"]) \
        / jnp.linalg.norm(want["head_grad"])
    return {"loss0_gap": rel[0], "loss_gap": max(rel[1:]),
            "batch_var_err": batch_var_err(got["batch_stats"],
                                           want["batch_stats"]),
            "grad_norm_gap": grad[len(grad) // 2],
            "delta_norm_gap": delta[len(delta) // 2],
            "seen_only": {"head_grad_err": float(head),
                          "grad_norm_gap_worst_leaf": grad[-1],
                          "delta_norm_gap_worst_leaf": delta[-1]}}


def macs_per_item(config: dict) -> float:
    """Multiply-accumulates of one forward pass of one image, from the
    shapes: every convolution at its output resolution, and the
    classifier.  (BatchNorm, pooling and the additions are left out, as
    the usual count does.)"""
    size = -(-config["image"] // 2)          # after the stem's stride
    total = 64 * config["in_channels"] * 49 * size * size
    size = -(-size // 2)                     # after the max pool
    inp = 64
    for width, blocks, stride in config["stages"]:
        for bi in range(blocks):
            s = stride if bi == 0 else 1
            out = -(-size // s)
            total += width * inp * out * out           # a: 1x1, strided
            total += width * width * 9 * out * out     # b: 3x3
            total += width * 4 * width * out * out     # c: 1x1
            if bi == 0:
                total += width * 4 * inp * out * out   # shortcut
            inp, size = width * 4, out
    return float(total + inp * config["num_classes"])


def train_flops_per_item(config: dict) -> float:
    """Operations the forward and backward passes require for one image:
    2 per multiply-accumulate, and the backward pass twice the forward."""
    return 3.0 * 2.0 * macs_per_item(config)
