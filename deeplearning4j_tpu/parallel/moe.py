"""Mixture-of-Experts with expert parallelism (EP).

Reference: **ABSENT in the reference** (SURVEY.md §2.6 — no MoE/EP).  A NEW
capability, built the TPU way:

- :func:`moe_apply` — dense dispatch: top-k gating as one-hot einsums, all
  experts evaluated as a single batched matmul (E folded into the
  contraction).  Under ``pjit`` with the expert dim sharded over the
  ``model`` axis, GSPMD partitions it automatically — this is the
  recommended single-executable path.
- :func:`moe_apply_expert_parallel` — explicit EP under ``shard_map``:
  tokens route to their expert's device group with ``lax.all_to_all`` over
  the expert axis (fixed capacity per expert, overflow dropped to the
  residual path like Switch-Transformer), experts compute locally, results
  return with the inverse all_to_all.  Use when the expert count is too
  large for GSPMD's dense dispatch to keep weights resident.

Auxiliary load-balancing loss follows Switch (mean fraction * mean prob).

The SERVED expert layer is a third thing: one chip's share of an
expert-parallel deployment, without the exchange.  It is told which
experts it holds, routes every token over ALL experts
(:func:`route_sigmoid_topk`), computes the part of the layer's output
that its own experts contribute and leaves the rest out; no capacity
factor, so no token is ever dropped.  Three forms of the same sum:
:func:`moe_share_dense` (every held expert over every token: the
reference form, and what runs on the CPU or over several devices),
:func:`moe_share_step` (a decode step, where the experts' bytes bound the
time: lowered for one TPU it is a kernel that reads only the held experts
a real token of the step chose, their ids scalar-prefetched; elsewhere
the dense form) and :func:`moe_share_grouped` (pairs sorted by expert,
each expert's matrices over the rows of its own group: a prefill, where
all-over-all would be 16 times the work; lowered for one TPU a pass is
one kernel that walks the (row tile, expert) pairs that meet and streams
each such expert's weights once, elsewhere one ``lax.ragged_dot`` a
projection).  :func:`moe_share_counts` counts what was routed where.  An
expert is what the caller hands over: three matrices ``Eg, Eu, Ed`` and
``act(g, u)`` of both pre-activations (``silu(g) * u`` where none is
given), or, with ``Eg=None``, two matrices and ``act(u)`` of the one
(:func:`relu2`): ``Ed act(x Eu)``, of whatever widths in and out.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.nn.conf.attention import lowered_for_one_tpu
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import BaseLayer

__all__ = ["init_moe", "moe_apply", "moe_apply_expert_parallel",
           "MoELayer", "MoEFeedForwardLayer", "route_sigmoid_topk",
           "route_softmax_topk",
           "moe_share_dense", "moe_share_step", "moe_share_grouped",
           "moe_share_counts", "moe_step_kernel_lowerings",
           "moe_grouped_kernel_lowerings", "relu2"]


def init_moe(key, n_experts: int, d_in: int, d_hidden: int, d_out: int,
             dtype=jnp.float32):
    """Params for E two-layer MLP experts + a router."""
    kr, k1, k2 = jax.random.split(key, 3)
    s1 = (2.0 / (d_in + d_hidden)) ** 0.5
    s2 = (2.0 / (d_hidden + d_out)) ** 0.5
    return {
        "router": jax.random.normal(kr, (d_in, n_experts), dtype) * 0.02,
        "W1": jax.random.normal(k1, (n_experts, d_in, d_hidden), dtype) * s1,
        "b1": jnp.zeros((n_experts, d_hidden), dtype),
        "W2": jax.random.normal(k2, (n_experts, d_hidden, d_out), dtype) * s2,
        "b2": jnp.zeros((n_experts, d_out), dtype),
    }


def _gate(params, x, top_k: int):
    logits = x @ params["router"]                    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    if top_k == 1:
        idx = jnp.argmax(probs, axis=-1)             # (T,)
        gates = jnp.max(probs, axis=-1, keepdims=True)
        topi = idx[:, None]
    else:
        gates, topi = lax.top_k(probs, top_k)        # (T, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, topi


def _aux_loss(probs, topi, n_experts: int):
    """Switch load-balance loss: E * mean(frac_tokens_e) . mean(prob_e)."""
    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], n_experts), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac * mean_prob)


def moe_apply(params, x, top_k: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense-dispatch MoE: (T, d_in) -> ((T, d_out), aux_loss).

    One-hot dispatch einsums — no gather/scatter, so GSPMD shards the E dim
    of every tensor over the ``model`` axis without host logic.
    """
    E = params["router"].shape[1]
    probs, gates, topi = _gate(params, x, top_k)
    disp = jax.nn.one_hot(topi, E, dtype=x.dtype)      # (T, k, E)
    comb = disp * gates[..., None]                     # (T, k, E)
    xe = jnp.einsum("tke,td->etd", disp, x)            # route tokens in
    h = jax.nn.relu(jnp.einsum("etd,edh->eth", xe, params["W1"])
                    + params["b1"][:, None, :])
    ye = jnp.einsum("eth,eho->eto", h, params["W2"]) + params["b2"][:, None, :]
    y = jnp.einsum("tke,eto->to", comb, ye)            # weighted combine
    return y, _aux_loss(probs, topi, E)


def moe_apply_expert_parallel(mesh, params, x, capacity_factor: float = 1.25,
                              axis_name: str = "model"
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 (Switch) MoE with explicit all_to_all expert dispatch.

    Experts are sharded over ``axis_name`` (E divisible by its size); the
    token batch is sharded over ``data``.  Per shard: route local tokens to
    capacity slots per expert, all_to_all to expert owners, compute, inverse
    all_to_all home.  Overflow tokens pass through (residual), as in Switch.
    """
    jmesh = getattr(mesh, "mesh", mesh)
    ep = jmesh.shape[axis_name]
    E = params["router"].shape[1]
    if E % ep:
        raise ValueError(f"{E} experts not divisible by axis size {ep}")

    def local(params, x_loc):
        T = x_loc.shape[0]
        E_loc = E // ep
        cap = max(1, int(capacity_factor * T / E))
        probs, gates, topi = _gate(params, x_loc, 1)
        eidx = topi[:, 0]                              # (T,)
        # position of each token within its expert's capacity window
        onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot      # 1-based slot
        slot = (pos.sum(-1) - 1)                       # (T,)
        keep = slot < cap
        islot = jnp.clip(slot, 0, cap - 1)
        # dispatch buffer (E, cap, d) -> (ep, E_loc, cap, d): piece p of dim
        # 0 ships to device p of the expert axis
        disp = jnp.zeros((E, cap, x_loc.shape[1]), x_loc.dtype)
        disp = disp.at[eidx, islot].add(x_loc * keep[:, None])
        disp = disp.reshape(ep, E_loc, cap, -1)
        # leading-axis exchange (split=concat=0, its own transpose): after
        # it, dim 0 indexes the SOURCE device, dim 1 the local expert
        recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0)
        # expert weights arrive ALREADY sharded over the expert axis (the
        # whole point of EP: each device holds only its E_loc experts)
        h = jax.nn.relu(jnp.einsum("pecd,edh->pech", recv, params["W1"])
                        + params["b1"][None, :, None, :])
        ye = jnp.einsum("pech,eho->peco", h, params["W2"]) \
            + params["b2"][None, :, None, :]
        # inverse exchange brings each token's result home
        back = lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0)
        back = back.reshape(E, cap, -1)
        y = back[eidx, islot]
        y = jnp.where(keep[:, None], y * gates, x_loc)   # overflow: residual
        aux = lax.pmean(_aux_loss(probs, topi, E), "data")
        return y, aux

    # router replicated (every token gates locally); expert tensors sharded
    # on their leading E dim — each device materialises only E/ep experts
    pspec = {k: (P() if k == "router" else P(axis_name))
             for k in params}
    # check_vma off: the pmean'd aux IS replicated, but the static checker
    # can't prove it through the data-dependent dispatch
    fn = jax.shard_map(local, mesh=jmesh,
                       in_specs=(pspec, P("data")),
                       out_specs=(P("data"), P()), check_vma=False)
    return fn(params, x)


# -- one chip's share of an expert-parallel layer (the serving tier) -----

def route_sigmoid_topk(x, Wr, k: int, scale: float):
    """Sigmoid gate over ALL experts, in float32: ``x (T, d)``, ``Wr (d,
    E)`` -> the ``k`` largest of ``sigmoid(x Wr)`` as ``(idx (T, k)
    int32, w (T, k))`` with ``w = g / (sum of the k + 1e-20) * scale``.
    The matmul is float32 at ``HIGHEST``: which expert comes eighth is
    decided by differences a bfloat16 pass would not see."""
    g = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), Wr.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    top, idx = lax.top_k(g, k)
    w = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def route_softmax_topk(x, Wr, k: int):
    """Softmax gate over ALL experts, in float32 (Qwen3-MoE's router with
    ``norm_topk_prob``): ``x (T, d)``, ``Wr (d, E)`` -> the ``k`` largest
    of ``softmax(x Wr)`` as ``(idx (T, k) int32, w (T, k))`` with ``w = g /
    (sum of the k)``.  Float32 at ``HIGHEST`` for the reason
    :func:`route_sigmoid_topk` gives."""
    g = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), Wr.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    top, idx = lax.top_k(g, k)
    return idx.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)


def route_sigmoid_group_topk(x, Wr, bias, k: int, n_group: int,
                             topk_group: int, scale: float):
    """Sigmoid gate over ALL experts with GROUP-LIMITED choice and a
    correction bias (DeepSeek-V3's ``noaux_tc``), in float32: ``x (T,
    d)``, ``Wr (d, E)``, ``bias (E,)``.  ``s = sigmoid(x Wr)``; the choice
    is made on ``c = s + bias``: the experts lie in ``n_group`` groups of
    ``E / n_group``, a group's score is the sum of its two largest ``c``,
    the ``topk_group`` best groups stay, and among their experts the ``k``
    largest ``c`` are chosen.  The weights are free of the bias: ``w =
    s_chosen / (sum of the k) * scale``.  Returns ``(idx (T, k) int32, w
    (T, k))``.  Float32 at ``HIGHEST`` for the reason
    :func:`route_sigmoid_topk` gives."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), Wr.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    c = s + bias.astype(jnp.float32)
    T, E = c.shape
    grouped = c.reshape(T, n_group, E // n_group)
    _, best = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], axis=-1),
                        topk_group)                          # (T, topk_group)
    stays = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)
    _, idx = lax.top_k(jnp.where(stays[..., None], grouped, -jnp.inf
                                 ).reshape(T, E), k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx.astype(jnp.int32), \
        top / jnp.sum(top, axis=-1, keepdims=True) * scale


def _held(idx, lo: int, n: int, real):
    """``(idx - lo, held here)`` for the chosen experts ``idx (T, k)`` of
    the real tokens ``real (T,)``."""
    e = idx - lo
    return e, (e >= 0) & (e < n) & real[:, None]


def _hit(idx, lo: int, n: int, real):
    """``(n,)`` bool: the held experts that a real token chose."""
    e, here = _held(idx, lo, n, real)
    return jnp.any(here[..., None] & (e[..., None] == jnp.arange(n)),
                   axis=(0, 1))


def moe_share_counts(idx, lo: int, n: int, real):
    """``[pairs routed here, pairs whose expert is absent, held experts
    with a token]`` as int32, over the real tokens."""
    routed = jnp.sum(_held(idx, lo, n, real)[1])
    return jnp.stack([routed, idx.shape[1] * jnp.sum(real) - routed,
                      jnp.sum(_hit(idx, lo, n, real))]).astype(jnp.int32)


def _share_weights(idx, w, lo: int, n: int, real=None):
    """``c (T, n)`` float32: the token's weight for expert ``lo + e``, 0
    where it did not choose it (and for a token that is not ``real``)."""
    c = jnp.sum(jnp.where(
        (idx - lo)[..., None] == jnp.arange(n), w[..., None],
        jnp.float32(0)), axis=1)
    return c if real is None else jnp.where(real[:, None], c, jnp.float32(0))


def _silu_gate(g, u):
    return jax.nn.silu(g) * u


def relu2(u):
    """``relu(u)²``: the activation of an expert of two matrices."""
    return jnp.square(jax.nn.relu(u))


def _expert(Eg, Eu, act):
    """``(the matrices x is multiplied by, the activation of their
    products)``: gate and up under ``act(g, u)`` (``silu(g) * u`` where
    none is given), or with ``Eg=None`` the one matrix under ``act(u)``."""
    if Eg is None:
        if act is None:
            raise ValueError("an expert of two matrices names its "
                             "activation: act(u)")
        return (Eu,), act
    return (Eg, Eu), act or _silu_gate


def moe_share_dense(x, idx, w, Eg, Eu, Ed, lo: int, real=None, act=None):
    """The held experts' part of the layer's output, every held expert
    over every token: ``sum_e c[t, e] Ed_e(silu(x Eg_e) * x Eu_e)`` with
    ``c`` the token's weight for expert ``lo + e``, 0 where it did not
    choose it (and, given ``real (T,)``, for a token that is not real).
    ``x (T, d)``; ``Eg, Eu (n, d, f)``, ``Ed (n, f, d')``; float32 out.
    With ``Eg=None`` an expert is ``Ed_e act(x Eu_e)`` (see the module's
    docstring).  The down-projection contracts experts and width at
    once, so the weighted sum over experts is inside one matmul."""
    ins, act = _expert(Eg, Eu, act)
    n, _, f = Eu.shape
    T = x.shape[0]
    dt = Eu.dtype
    x = x.astype(dt)
    c = _share_weights(idx, w, lo, n, real)
    up = lambda W: jnp.einsum("td,edf->tef", x, W,
                              preferred_element_type=jnp.float32)
    h = act(*map(up, ins)) * c[..., None]                     # (T, n, f)
    return jnp.matmul(h.reshape(T, n * f).astype(dt),
                      Ed.reshape(n * f, -1),
                      preferred_element_type=jnp.float32)


# -- the step's form: only the held experts that were hit ----------------

#: lanes of an expert's width ``f`` a place of the kernel's grid works on:
#: three blocks (gate and up ``(d, tile)``, down ``(tile, d)``), each
#: double-buffered by the pipeline: at d = 7,680 in bfloat16 7.86 MB a
#: block, 47 MB of the 64 MB the call asks for.  Measured alone on a v5e
#: at ``(32, 7680) x (16, 7680, 2048)`` with 4 / 10 / 16 experts hit (PR
#: 37): 702 / 732 / 741 GB/s over the hit experts' bytes, where tiles of
#: 256 lanes read 678 / 706 / 713 (a ``(d, 256)`` column block is 480
#: runs of 8 KB) and 1,024 do not fit.  An expert of two matrices at
#: ``(1024, 2688)`` / ``(2688, 1024)`` takes its width WHOLE
#: (:func:`_expert_tile`): inside a step of 64 slots with ~490 of 640 held
#: experts hit the five calls read 749 GB/s, 91.4% of the peak (a traced
#: run of ``nemotron3_super.agent_closed64``, PR 48); three tiles of 896
#: lanes were not timed
_EXPERT_TILE = 512
#: what the blocks of one place may take of the 64 MB, double-buffered
_EXPERT_BLOCKS_BYTES = 48 << 20


def _expert_tile(f: int, rows: int, itemsize: int) -> int:
    """Lanes of the width ``f`` a place works on, ``rows`` being the
    weights' rows a lane of the width brings (``d`` for each matrix into
    the width, ``d'`` for the one out of it): :data:`_EXPERT_TILE` where
    it divides ``f``; else the width whole, or where that does not fit
    the largest part of it in whole lane tiles that does (2,688 = 21 x
    128 at 1,024 rows twice: whole, 22 MB)."""
    if f % _EXPERT_TILE == 0:
        return _EXPERT_TILE
    fits = lambda t: 2 * rows * t * itemsize <= _EXPERT_BLOCKS_BYTES
    if fits(f) or f % 128:
        return f
    return max(t for t in range(128, f, 128) if f % t == 0 and fits(t))


def _hit_list(idx, lo: int, n: int, real):
    """``(hit (n,) int32, nhit)``: the held experts a real token chose, in
    rising order at the front of ``hit`` (a cumsum places each: no sort);
    the entries past ``nhit`` name expert 0."""
    chosen = _hit(idx, lo, n, real)
    at = jnp.arange(n, dtype=jnp.int32)
    place = jnp.cumsum(chosen) - 1
    hit = jnp.sum(jnp.where(chosen & (place == at[:, None]), at, 0), axis=1)
    return hit.astype(jnp.int32), jnp.sum(chosen).astype(jnp.int32)


def _hit_kernel(act, _hit_ref, x_ref, c_ref, *refs):
    """One place of the grid: one tile of one hit expert's width.  ``x``
    (all rows) against the tile's columns of the gate and the up
    projection (or of the one matrix of an expert of two), the token's
    weight for this expert on their activation, and the tile's rows of
    the down projection added into ``o_ref``, which stays in VMEM over
    the whole grid."""
    f32 = jnp.float32
    *in_refs, d_ref, o_ref = refs

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    x = x_ref[...]
    up = lambda ref: jnp.dot(x, ref[...], preferred_element_type=f32)
    h = act(*map(up, in_refs)) * c_ref[...]                   # (T, tile)
    o_ref[...] += jnp.dot(h.astype(d_ref.dtype), d_ref[...],
                          preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _hit_call(hit, nhit, x, c, *E, act=_silu_gate, interpret):
    """The kernel's call: ``x (T, d)`` in the weights' dtype, ``c (n, T,
    1)`` float32, ``T`` whole sublane tiles, ``E`` the matrices into the
    width and, last, the one out of it.  The grid walks ``nhit``
    experts x the tiles of their width; the index maps name the blocks of
    expert ``hit[i]`` in the stacked weights, which go in whole (no
    expert is sliced out or copied first), and the pipeline copies the
    next place's three blocks while this one computes.  With no expert
    hit the grid still walks one (``hit[0]`` = 0, whose weights ``c`` are
    all 0: the output block is written, as zeros).  A jit of its own with
    the weights as arguments: every expert layer of a step is then the
    same computation, traced and lowered to Mosaic once a program (see
    ``nn/conf/attention.py:_pages_call``)."""
    *ins, Ed = E
    n, d, f = ins[0].shape
    T, dout = x.shape[0], Ed.shape[-1]
    tile = _expert_tile(f, len(ins) * d + dout, Ed.dtype.itemsize)
    # index maps: ``i * 0`` and not ``0`` (the package enables x64, and a
    # bare literal would be an int64 Mosaic has not)
    return pl.pallas_call(
        functools.partial(_hit_kernel, act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.maximum(nhit, 1), f // tile),
            in_specs=[
                pl.BlockSpec((T, d), lambda i, j, hit: (i * 0, i * 0)),
                pl.BlockSpec((None, T, 1),
                             lambda i, j, hit: (hit[i], i * 0, i * 0)),
                *(pl.BlockSpec((None, d, tile),
                               lambda i, j, hit: (hit[i], i * 0, j))
                  for _ in ins),
                pl.BlockSpec((None, tile, dout),
                             lambda i, j, hit: (hit[i], j, i * 0)),
            ],
            out_specs=pl.BlockSpec((T, dout),
                                   lambda i, j, hit: (i * 0, i * 0))),
        out_shape=jax.ShapeDtypeStruct((T, dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="moe_share_step",
        interpret=interpret,
    )(hit, x, c, *E)


def _share_hit(x, idx, w, Eg, Eu, Ed, lo: int, real, act=None,
               interpret=False):
    """:func:`moe_share_dense`'s sum over the held experts that a real
    token of ``x`` chose, expert by expert, through the kernel: an expert
    nobody chose is not read.  Same operands and precision as the dense
    form (the weights' dtype into the MXU, float32 sums, the float32
    ``c``); the sum over experts runs in the order of the hit list where
    the dense form contracts them in one matmul.  Rows are padded to
    whole sublane tiles (16: a bfloat16 tile)."""
    ins, act = _expert(Eg, Eu, act)
    n = Eu.shape[0]
    T = x.shape[0]
    pad = -T % 16
    c = jnp.pad(_share_weights(idx, w, lo, n, real), ((0, pad), (0, 0)))
    out = _hit_call(*_hit_list(idx, lo, n, real),
                    jnp.pad(x.astype(Eu.dtype), ((0, pad), (0, 0))),
                    c.T[..., None], *ins, Ed, act=act, interpret=interpret)
    return out[:T]


#: how often the step's expert layer was lowered as the kernel (program
#: telemetry: the batcher's gauge reads it around its warm-up)
_stepKernelLowerings = [0]


def moe_step_kernel_lowerings() -> int:
    """How many times :func:`moe_share_step` has been lowered as the TPU
    kernel in this process (once a program built for one TPU, whose
    expert layers of one shape share the lowering; never on the CPU or
    for several devices)."""
    return _stepKernelLowerings[0]


def _share_step_lowering(ctx, *args, lo, act):
    kernel = lowered_for_one_tpu(ctx)
    _stepKernelLowerings[0] += kernel
    form = _share_hit if kernel else moe_share_dense

    def lowered(x, idx, w, *E, real):
        # three matrices an expert, or two: no gate
        return form(x, idx, w, *((None,) * (3 - len(E)) + E), lo, real,
                    act=act)
    return mlir.lower_fun(lambda *a: lowered(*a[:-1], real=a[-1]),
                          multiple_results=False)(ctx, *args)


_share_step_p = jex_core.Primitive("moe_share_step")


@functools.partial(jax.jit, static_argnames=("lo", "act"))
def _share_step_eager(*args, lo, act):
    """Outside any jit the primitive runs as a program of its own."""
    return _share_step_p.bind(*args, lo=lo, act=act)


_share_step_p.def_impl(_share_step_eager)
_share_step_p.def_abstract_eval(
    lambda x, idx, w, *E_real, lo, act: jax.core.ShapedArray(
        (x.shape[0], E_real[-2].shape[-1]), jnp.float32))
mlir.register_lowering(_share_step_p, _share_step_lowering)


def moe_share_step(x, idx, w, Eg, Eu, Ed, lo: int, real, act=None):
    """:func:`moe_share_dense` as a decode step runs it, where the
    experts' bytes are the time: an expert's weights are read only if a
    ``real (T,)`` token of this step chose it.  Chosen by what the
    program is lowered for, not by a knob (the rule of
    ``paged_attention``): one TPU -> the kernel over the hit experts
    (:func:`_share_hit`); the CPU or several devices -> the dense form.
    The rows of tokens that are not real come back as zeros in both.
    ``Eg=None`` and ``act`` as :func:`moe_share_dense` takes them."""
    E = (Eu, Ed) if Eg is None else (Eg, Eu, Ed)
    return _share_step_p.bind(x, idx, w, *E, real, lo=lo,
                              act=_expert(Eg, Eu, act)[1])


# -- the prefill's form: pairs sorted by expert, one grouped pass ---------

#: rows of the sorted pairs a place of the grouped kernel's grid works on.
#: A visit multiplies the WHOLE tile by its expert's blocks and masks the
#: rows outside the expert's group, so a group's two edges cost up to a
#: tile of wasted rows each: 128 is the fewest rows that keep the MXU's
#: 128 x 128 weight tiles busy, and at 819 GB/s over 197 TFLOP/s a tile of
#: up to ~240 rows multiplies a freshly streamed block in less time than
#: the next block takes to arrive.  Alone on a v5e tiles of 256 rows read
#: the same to 1% at every caller's shape they fit (PERF.md section 5)
_GROUP_ROWS = 128


def _rows_ragged(xs, wrow, edge, *E, act):
    """A pass's rows through their experts, the compiler's way: one
    ``lax.ragged_dot`` a matrix over the rows of each expert's group.
    ``xs (R, d)`` the sorted pairs' tokens, ``wrow (R,)`` float32 the
    pair's weight (0 for a dead row), ``edge (n,)`` where each expert's
    group ends among the rows; ``E`` the matrices into the width and,
    last, the one out of it.  Returns ``(R, d')`` float32."""
    *ins, Ed = E
    sizes = jnp.diff(edge, prepend=0).astype(jnp.int32)
    gmm = lambda a, W: lax.ragged_dot(a, W, sizes,
                                      preferred_element_type=jnp.float32)
    h = act(*(gmm(xs, W) for W in ins)) * wrow[:, None]
    return gmm(h.astype(Ed.dtype), Ed)


def _visits(edge, tile: int, tiles: int):
    """The grouped kernel's walk: every (row tile, expert) whose group
    has a row in the tile, tiles rising and experts rising within a tile,
    as ``(tile, expert, group start, group end)`` of ``tiles + n - 1``
    int32 each (the most there can be: a group's first tile is no earlier
    than the last of the group before) and how many are real.  Built
    with comparisons and a cumsum: no sort, no loop."""
    n = edge.shape[0]
    start = jnp.concatenate([jnp.zeros((1,), edge.dtype), edge[:-1]])
    first = start // tile
    count = jnp.where(edge > start, (edge - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(count)
    v = jnp.arange(tiles + n - 1, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(v[:, None] >= upto, axis=1), n - 1)
    # one gather: the tile of the expert's first visit less that visit's
    # place in the walk, and the group's bounds
    base, lo, hi = jnp.stack([first - (upto - count), start, edge])[:, e]
    i32 = lambda a: a.astype(jnp.int32)
    return i32(jnp.clip(base + v, 0, tiles - 1)), i32(e), i32(lo), i32(hi), \
        i32(upto[-1])


def _grouped_kernel(act, vt_ref, _ve_ref, lo_ref, hi_ref, x_ref, w_ref,
                    *refs):
    """One place of the grid: one visit (a tile of the sorted rows x an
    expert with rows in it) x one tile of the expert's width.  The
    tile's rows against the tile's columns of the matrices into the
    width, the pair's weight on their activation -- 0 for the rows that
    are not this expert's -- and the tile's rows of the matrix out of it
    added into ``o_ref``, which stays in VMEM over the row tile's
    visits."""
    f32 = jnp.float32
    *in_refs, d_ref, o_ref = refs
    v, j = pl.program_id(0), pl.program_id(1)
    rows = x_ref.shape[0]

    @pl.when((j == 0) & ((v == 0)
                         | (vt_ref[v] != vt_ref[jnp.maximum(v - 1, 0)])))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    row = vt_ref[v] * jnp.int32(rows) \
        + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    mine = (row >= lo_ref[v]) & (row < hi_ref[v])
    x = x_ref[...]
    up = lambda ref: jnp.dot(x, ref[...], preferred_element_type=f32)
    h = act(*map(up, in_refs)) * jnp.where(mine, w_ref[...], f32(0))
    o_ref[...] += jnp.dot(h.astype(d_ref.dtype), d_ref[...],
                          preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("act", "rows", "interpret"))
def _grouped_call(vt, ve, lo, hi, nvis, xs, wrow, *E, act, rows, interpret):
    """The grouped kernel's call: ``xs (R, d)`` in the weights' dtype,
    ``wrow (R, 1)`` float32, ``R`` whole tiles of ``rows``; the walk of
    :func:`_visits` scalar-prefetched, the grid's first dimension its
    ``nvis`` real visits (one at least).  The index maps name the blocks
    of expert ``ve[v]`` in the stacked weights, which go in whole (as in
    :func:`_hit_call`): consecutive visits of one expert name the same
    blocks, which the pipeline then does not copy again (where the width
    is one tile), and an expert with no row in the pass is never named.
    The rows of a tile no visit names are not written.  A jit of its own
    for the reason :func:`_hit_call` gives."""
    *ins, Ed = E
    _, d, f = ins[0].shape
    dout = Ed.shape[-1]
    tile = _expert_tile(f, len(ins) * d + dout, Ed.dtype.itemsize)
    at = lambda v, j, vt, ve, lo, hi: (vt[v], v * 0)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(nvis, 1), f // tile),
            in_specs=[
                pl.BlockSpec((rows, d), at),
                pl.BlockSpec((rows, 1), at),
                *(pl.BlockSpec((None, d, tile),
                               lambda v, j, vt, ve, lo, hi: (ve[v], v * 0, j))
                  for _ in ins),
                pl.BlockSpec((None, tile, dout),
                             lambda v, j, vt, ve, lo, hi: (ve[v], j, v * 0)),
            ],
            out_specs=pl.BlockSpec((rows, dout), at)),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="moe_share_grouped",
        interpret=interpret,
    )(vt, ve, lo, hi, xs, wrow, *E)


def _rows_kernel(xs, wrow, edge, *E, act, rows=None, interpret=False):
    """:func:`_rows_ragged`'s product through the grouped kernel: every
    expert with a row in the pass streams its weights once, from where
    they lie, under the matmuls of its rows; the activations between the
    two projections never leave VMEM.  Same operands and precision (the
    weights' dtype into the MXU, float32 sums, the float32 pair weight
    on the activation).  ``rows`` a tile: :data:`_GROUP_ROWS`, or all of
    a pass that has fewer (in whole sublane tiles of 16; the pass is
    padded to whole tiles)."""
    R = xs.shape[0]
    rows = rows or min(_GROUP_ROWS, R + -R % 16)
    pad = -R % rows
    out = _grouped_call(
        *_visits(edge, rows, (R + pad) // rows),
        jnp.pad(xs, ((0, pad), (0, 0))),
        jnp.pad(wrow, (0, pad))[:, None], *E,
        act=act, rows=rows, interpret=interpret)
    return out[:R]


#: how often a pass of the grouped form was lowered as the kernel
_groupedKernelLowerings = [0]


def moe_grouped_kernel_lowerings() -> int:
    """How many times a pass of :func:`moe_share_grouped` has been
    lowered as the TPU kernel in this process (once a program built for
    one TPU, whose expert layers of one shape share the lowering; never
    on the CPU or for several devices)."""
    return _groupedKernelLowerings[0]


def _grouped_rows_lowering(ctx, *args, act):
    """A pass's rows through their experts, chosen by what the program is
    lowered for, not by a knob (the rule of ``paged_attention``): one TPU
    -> the grouped kernel (:func:`_rows_kernel`); the CPU or several
    devices -> the compiler's ``lax.ragged_dot`` (:func:`_rows_ragged`)."""
    kernel = lowered_for_one_tpu(ctx)
    _groupedKernelLowerings[0] += kernel
    return mlir.lower_fun(
        functools.partial(_rows_kernel if kernel else _rows_ragged, act=act),
        multiple_results=False)(ctx, *args)


_grouped_rows_p = jex_core.Primitive("moe_share_grouped_rows")


@functools.partial(jax.jit, static_argnames=("act",))
def _grouped_rows_eager(*args, act):
    """Outside any jit the primitive runs as a program of its own."""
    return _grouped_rows_p.bind(*args, act=act)


_grouped_rows_p.def_impl(_grouped_rows_eager)
_grouped_rows_p.def_abstract_eval(
    lambda xs, wrow, edge, *E, act: jax.core.ShapedArray(
        (xs.shape[0], E[-1].shape[-1]), jnp.float32))
mlir.register_lowering(_grouped_rows_p, _grouped_rows_lowering)


def moe_share_grouped(x, idx, w, Eg, Eu, Ed, lo: int, real, passRows=None,
                      act=None):
    """:func:`moe_share_dense`'s sum by GROUPS: the token-expert pairs
    whose expert is held, sorted by expert, and each expert's matrices
    over the rows of its group alone -- the work of the pairs that exist
    (half a pair a token for 16 of 256 experts at 8 a token), not of
    ``n`` experts over every token.

    Nothing is dropped and no shape depends on the routing: the sorted
    pairs are taken ``passRows`` rows a pass (``T`` where omitted), for as
    many passes as hold a held pair (one, unless the router leans on this
    chip's experts; ``k`` at most).  A pass gathers its rows' tokens,
    multiplies by group (the primitive ``moe_share_grouped_rows``:
    lowered for one TPU a kernel that streams the weights of each expert
    with a row in the pass once, at half to four fifths of the HBM's
    pace; on the CPU or for several devices one ``lax.ragged_dot`` a
    projection, which streams ALL the held experts' weights whatever the
    rows, at an eighth to a third of it: PERF.md section 5), and adds
    each row's weighted output to its token through a 0/1 matrix on the
    MXU (``T x passRows``; a scatter-add of rows would serialise).  A
    pass costs its experts' bytes whatever its rows, so a
    share that expects more than one held pair a token (128 of 512
    experts at 8 a token: two) names the rows that hold them in ONE pass.
    ``Eg=None`` and ``act`` as :func:`moe_share_dense` takes them."""
    return _share_grouped(_grouped_rows_p.bind, x, idx, w, Eg, Eu, Ed, lo,
                          real, passRows, act)


def _share_grouped(through, x, idx, w, Eg, Eu, Ed, lo, real, passRows, act):
    """:func:`moe_share_grouped` with a pass's rows taken ``through`` the
    given form: the primitive's ``bind``, or :func:`_rows_ragged` /
    :func:`_rows_kernel` by name (a test hands the kernel in interpret
    mode)."""
    ins, act = _expert(Eg, Eu, act)
    T, k = idx.shape
    R = T if passRows is None else passRows
    n = Eu.shape[0]
    dt = Eu.dtype
    f32 = jnp.float32
    x = x.astype(dt)
    e, here = _held(idx, lo, n, real)
    key = jnp.where(here, e, n).reshape(-1)        # absent pairs last
    order = jnp.argsort(key).astype(jnp.int32)                   # (T k,)
    if T * k % R:        # whole passes: the rows behind the pairs are dead
        order = jnp.pad(order, (0, -(T * k) % R))
    ends = jnp.cumsum(jnp.sum(
        key[:, None] == jnp.arange(n), axis=0)).astype(jnp.int32)
    total = ends[-1]
    wflat = w.reshape(-1)
    at = jnp.arange(R, dtype=jnp.int32)

    def one_pass(carry):
        b, out = carry
        rows = lax.dynamic_slice_in_dim(order, b * R, R)
        live = b * R + at < total
        edge = jnp.clip(ends - b * R, 0, R)
        tok = rows // k
        y = jnp.where(live[:, None], through(
            x[tok], jnp.where(live, wflat[rows], f32(0)), edge, *ins, Ed,
            act=act), f32(0))
        home = (jnp.arange(T, dtype=jnp.int32)[:, None] == tok[None, :]) \
            & live[None, :]                                      # (T, R)
        return b + 1, out + jnp.matmul(home.astype(dt), y.astype(dt),
                                       preferred_element_type=f32)
    _, out = lax.while_loop(lambda c: c[0] * R < total, one_pass,
                            (jnp.int32(0), jnp.zeros((T, Ed.shape[-1]), f32)))
    return out


@dataclasses.dataclass
class MoEFeedForwardLayer(BaseLayer):
    """Mixture-of-Experts feed-forward block as a model-DSL layer —
    drop it into a ``NeuralNetConfiguration...list()`` stack and the
    model's ONE fused train step carries it; under a
    ``MeshTrainer``/``ShardingPlan`` with a ``model`` axis the expert
    dim of every expert tensor shards over that axis (EP), composed
    with DP/ZeRO-1 in the same executable.

    The Switch load-balancing loss reaches the training loss through the
    layer-state aux channel (``hasAuxLoss``): forward returns
    ``auxLossScale * aux`` in its state and
    ``MultiLayerNetwork._lossFn`` adds it — without it the router
    collapses onto one expert.
    """

    nIn: int = 0
    nOut: int = 0
    nExperts: int = 4
    hiddenSize: Optional[int] = None
    topK: int = 1
    auxLossScale: float = 0.01

    #: consumed by MultiLayerNetwork._auxLoss
    hasAuxLoss = True

    def preferredFormat(self):
        return "FF"

    def inferNIn(self, inputType):
        if not self.nIn:
            self.nIn = inputType.size

    def getOutputType(self, inputType):
        return InputType.feedForward(self.nOut)

    def initParams(self, key, inputType, dtype=jnp.float32):
        return init_moe(key, self.nExperts, self.nIn,
                        self.hiddenSize or 4 * self.nIn, self.nOut, dtype)

    def initState(self, inputType, dtype=jnp.float32):
        # declaring the aux slot up front keeps the state pytree
        # structure identical before/after the first step (no retrace)
        return {"auxLoss": jnp.zeros((), jnp.float32)}

    def weightParamKeys(self):
        return ("router", "W1", "W2")

    def expertParamKeys(self):
        """Params whose LEADING dim is the expert dim — the ShardingPlan
        shards it over the ``model`` (expert) axis when divisible."""
        return ("W1", "b1", "W2", "b2")

    def forward(self, params, x, train, key, state):
        x = self._dropin(x, train, key)
        y, aux = moe_apply(params, x, self.topK)
        return y, {"auxLoss": (self.auxLossScale * aux)
                   .astype(jnp.float32)}


class MoELayer:
    """Object wrapper for config-style use; see moe_apply for semantics.

    The Switch load-balancing loss from the last ``__call__`` is exposed as
    ``auxLoss`` — ADD IT to the training loss (scaled ~0.01) or the router
    collapses onto one expert.
    """

    def __init__(self, nIn: int, nOut: int, nExperts: int = 4,
                 hiddenSize: Optional[int] = None, topK: int = 1,
                 seed: int = 0):
        self.nIn, self.nOut, self.nExperts = nIn, nOut, nExperts
        self.hiddenSize = hiddenSize or 4 * nIn
        self.topK = topK
        self.params = init_moe(jax.random.PRNGKey(seed), nExperts, nIn,
                               self.hiddenSize, nOut)
        self.auxLoss = None

    def apply(self, params, x):
        """Pure form for jit/grad: returns (y, aux_loss)."""
        return moe_apply(params, x, self.topK)

    def __call__(self, x):
        y, self.auxLoss = moe_apply(self.params, x, self.topK)
        return y
