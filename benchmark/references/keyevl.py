"""Plain reference for the ``keyevl`` family: the language model of
Keye-VL-2.0-30B-A3B (a Qwen3-MoE decoder, arXiv:2505.09388, whose keys the
catalog's ``config`` matches one for one, with ``sa_config``: the lightning
indexer and top-k selection of DeepSeek-V3.2-Exp's sparse attention) as
ONE CHIP'S SHARE of a deployment: it is told which of the routed experts
it holds (``experts_held``), routes over all ``router_width`` experts, and
adds only its own experts' part.  The full causal forward pass in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision, one
sequence at a time, no cache, no batching, one layer at a time, the held
experts upcast one at a time, a block of 512 queries at a time against
every key, the selection by a plain stable sort of the float32 scores.
The vision tower is left out: the catalog's row carries none of its sizes,
and the cell's traffic is text.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed (bfloat16 leaves) and the family's
builder (``configs/keyevl.py``) hands the same arrays to the program.

The equations.  ``d`` 2048, ``x`` a ``(T, d)`` sequence, RMSNorm ``x /
sqrt(mean(x^2) + 1e-6) * g``.

*Block*: ``y = x + Attn(RMSNorm(x))``, ``out = y + MoE(RMSNorm(y))``; a
final RMSNorm; an untied head.

*Attention* on ``h``::

    q = h W_q -> 32 heads of 128;  k = h W_k, v = h W_v -> 4 heads of 128
    q <- RoPE(RMSNorm_q(q)),  k <- RoPE(RMSNorm_k(k))       a head
    query head a reads KV head a // 8
    o_{t,a} = sum_{s in S_t} softmax_{s in S_t}(q_{t,a} . k_{s,a//8}
              / sqrt(128)) v_{s,a//8};   Attn = concat_a(o) W_o

*Selector* on the same ``h``::

    qI_{t,j} = RoPE(h_t W_qI)_j        16 heads of 64
    kI_s     = RoPE(LayerNorm(h_s W_kI))   ONE key of 64 for all heads
    w_t      = h_t W_w                 (16,)
    I_{t,s}  = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)      s <= t
    S_t      = the 2,048 positions of largest I_{t,s} (all while fewer
               are there; of equal scores the earlier position)

RoPE pairs lane ``i`` with lane ``i + D / 2`` and turns the pair by ``t *
theta^(-2 i / D)``, ``theta`` 1e7, ``t`` the position (text: the three
M-RoPE components are equal, the sections [16, 24, 24] change nothing).

*MoE* on ``u``: ``g = softmax(u W_r)`` over all 128, the 8 largest, ``w_e =
g_e / (sum of the 8)``; ``out = sum over chosen e in experts_held of w_e
W_d^e (silu(W_g^e u) * W_u^e u)``, an expert's width 768.  What the experts
outside ``experts_held`` would add is left out.

What ``config.json`` does not carry is listed in the configuration's file
under ``assumed``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STD = 0.02
HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
QUERY_BLOCK = 512
#: lengths are padded to a multiple of this, so a handful of programs
#: serve every request
LENGTH_BLOCK = 512


def dims(config: dict) -> dict:
    lo, hi = config["experts_held"]
    if hi - lo != config["num_experts"]:
        raise ValueError("experts_held does not name num_experts experts")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1 or not config["norm_topk_prob"] \
            or config["decoder_sparse_step"] != 1 \
            or config["mlp_only_layers"]:
        raise ValueError("written for one index key, normalised top-k "
                         "weights and an expert layer in every block")
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "G": config["num_key_value_heads"], "dh": config["head_dim"],
            "hI": sa["indexer_num_heads"], "dI": sa["indexer_head_dim"],
            "topk": sa["topk"], "fe": config["moe_intermediate_size"],
            "E": config["router_width"], "lo": lo, "n": hi - lo,
            "k": config["num_experts_per_tok"],
            "L": config["num_hidden_layers"], "V": config["vocab_size"],
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"])}


def published(config: dict) -> dict:
    """The configuration with the counts this chip's share cut put back
    as the source has them (``config["published"]``): all layers, all
    routed experts."""
    whole = dict(config, **config["published"])
    whole["experts_held"] = [0, whole["num_experts"]]
    return whole


def expert_layers(config: dict) -> int:
    """Layers with an expert layer: every one (``decoder_sparse_step`` 1,
    ``mlp_only_layers`` [])."""
    return dims(config)["L"]


def experts_held(config: dict) -> int:
    return dims(config)["n"]


# -- weights ---------------------------------------------------------------
def _normal(key, shape):
    return (STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("Dt",))
def _make_layer(key, Dt: tuple):
    D = dict(Dt)
    d, H, G, dh, fe = D["d"], D["H"], D["G"], D["dh"], D["fe"]
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 16))
    normal = lambda *shape: _normal(next(keys), shape)
    ones = lambda n: jnp.ones((n,), bf)
    p = {"norm_1": ones(d), "norm_2": ones(d),
         "attn": {"w_q": normal(d, H * dh), "w_k": normal(d, G * dh),
                  "w_v": normal(d, G * dh), "w_o": normal(H * dh, d),
                  "q_norm": ones(dh), "k_norm": ones(dh)},
         "indexer": {"w_q": normal(d, D["hI"] * D["dI"]),
                     "w_k": normal(d, D["dI"]), "k_norm": ones(D["dI"]),
                     "k_bias": jnp.zeros((D["dI"],), bf),
                     "w_w": normal(d, D["hI"])}}
    # an expert's weights depend on the seed and on its index among ALL
    # the routed experts, so the eight shares of a layer together hold
    # the experts the uncut layer holds
    base = next(keys)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(base, e), 3)
        return _normal(kg, (d, fe)), _normal(ku, (d, fe)), \
            _normal(kd, (fe, d))
    eg, eu, ed = jax.vmap(expert)(D["lo"] + jnp.arange(D["n"]))
    p["moe"] = {"w_router": normal(d, D["E"]),
                "experts": {"w_gate": eg, "w_up": eu, "w_down": ed}}
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    ke, kh = jax.random.split(key)
    return _normal(ke, (V, d)), _normal(kh, (d, V)), \
        jnp.ones((d,), jnp.bfloat16)


def make_weights(config: dict, key):
    """Seeded bfloat16 weights, made on the device, one small jitted
    program a layer; of the routed experts only those in
    ``experts_held``."""
    D = dims(config)
    Dt = tuple(sorted(D.items()))
    emb, head, norm_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    layers = [_make_layer(jax.random.fold_in(key, i + 1), Dt)
              for i in range(D["L"])]
    return {"emb": emb, "head": head, "norm_f": norm_f, "layers": layers}


# -- the forward pass -------------------------------------------------------
def _mm(a, w, low: bool):
    """A matmul with a weight, float32 at ``highest``.  ``low`` is the
    control: its input and its weight are rounded to float8 (e4m3)."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if low:
        a, w = a.astype(F8).astype(jnp.float32), w.astype(F8).astype(
            jnp.float32)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _layer_norm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32) + b.astype(jnp.float32)


def rope(x, theta: float):
    """``x (T, ..., D)`` with position ``t`` on the leading axis: lane
    ``i`` and lane ``i + D / 2`` turned by ``t * theta^(-2 i / D)``."""
    T, D = x.shape[0], x.shape[-1]
    half = D // 2
    inv = jnp.asarray(theta ** (-np.arange(half) / half), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32).reshape(
        (T,) + (1,) * (x.ndim - 1)) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def index_scores(h, p, D, low=False):
    """``(qI (T, 16, 64), kI (T, 64), w (T, 16))`` of the selector."""
    T = h.shape[0]
    qI = rope(_mm(h, p["w_q"], low).reshape(T, D["hI"], D["dI"]),
              D["theta"])
    kI = rope(_layer_norm(_mm(h, p["w_k"], low), p["k_norm"], p["k_bias"],
                          D["eps"]), D["theta"])
    return qI, kI, _mm(h, p["w_w"], low)


def selected(scores, valid, topk: int):
    """``(B, T)`` bool: for each row of float32 ``scores`` the ``topk``
    valid columns of largest score, by a stable descending sort (of equal
    scores the earlier column); every valid column where there are no
    more than ``topk``."""
    B, T = scores.shape
    order = jnp.argsort(-jnp.where(valid, scores, -jnp.inf), axis=-1,
                        stable=True)
    first = order[:, :min(topk, T)]
    chosen = jnp.zeros((B, T), bool).at[jnp.arange(B)[:, None], first].set(
        True)
    return chosen & valid


def _attention(h, p, pi, D, low, dense):
    """Sparse attention of one sequence as written: a block of queries at
    a time, their index scores against every key, the selection by a
    sort, softmax over the selected keys alone.  ``dense`` is the second
    control: the selection left out (every key ``s <= t``)."""
    T = h.shape[0]
    H, G, dh = D["H"], D["G"], D["dh"]
    r = H // G
    heads = lambda a, n: a.reshape(T, n, dh)
    q = rope(_rms(heads(_mm(h, p["w_q"], low), H), p["q_norm"], D["eps"]),
             D["theta"])
    k = rope(_rms(heads(_mm(h, p["w_k"], low), G), p["k_norm"], D["eps"]),
             D["theta"])
    v = heads(_mm(h, p["w_v"], low), G)
    qI, kI, w = index_scores(h, pi, D, low)
    B = min(T, QUERY_BLOCK)
    if T % B:
        raise ValueError(f"{T} positions are no multiple of {B}")
    keys = jnp.arange(T)[None, :]

    def block(i):
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * B, B)
        rows = i * B + jnp.arange(B)[:, None]
        keep = keys <= rows                                      # (B, T)
        if not dense:
            I = jnp.einsum("qj,qjk->qk", cut(w), jax.nn.relu(jnp.einsum(
                "qjd,kd->qjk", cut(qI), kI, precision=HI)), precision=HI)
            keep = selected(I, keep, D["topk"])
        s = jnp.einsum("qgrd,kgd->grqk", cut(q).reshape(B, G, r, dh), k,
                       precision=HI) / math.sqrt(dh)
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", a, v, precision=HI)
    o = lax.map(block, jnp.arange(T // B)).reshape(T, H * dh)
    return _mm(o, p["w_o"], low)


def route(x, w_router, D, low=False):
    """``(chosen experts (T, k), their weights (T, k))`` over all the
    router's outputs: softmax, the largest, normalised over the chosen."""
    g = jax.nn.softmax(_mm(x, w_router, low), axis=-1)
    top, idx = lax.top_k(g, D["k"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def expert_layer(x, m, D, low=False):
    """What the held experts add for ``x (T, d)``: every held expert over
    every token, weighted by the token's weight for it (zero where the
    token did not choose it), one expert at a time."""
    idx, w = route(x, m["w_router"], D, low)

    def one(out, ew):
        e, wg, wu, wd = ew
        c = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # (T,)
        y = _mm(jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low), wd, low)
        return out + c[:, None] * y, None
    ex = m["experts"]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (D["lo"] + jnp.arange(D["n"]), ex["w_gate"],
                       ex["w_up"], ex["w_down"]))
    return out


@functools.partial(jax.jit, static_argnames=("Dt", "low", "dense"))
def _layer(x, p, Dt: tuple, low: bool, dense: bool):
    """One layer over one sequence ``x (T, d)`` in float32."""
    D = dict(Dt)
    y = x + _attention(_rms(x, p["norm_1"], D["eps"]), p["attn"],
                       p["indexer"], D, low, dense)
    return y + expert_layer(_rms(y, p["norm_2"], D["eps"]), p["moe"], D, low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_f, head, eps: float, low: bool):
    return _mm(_rms(x, norm_f, eps), head, low)


def hidden(config: dict, weights, tokens, low: bool = False,
           dense: bool = False):
    """The last layer's output ``(len(tokens), d)`` for one sequence of
    token ids, one layer at a time."""
    Dt = tuple(sorted(dims(config).items()))
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for p in weights["layers"]:
        x = _layer(x, p, Dt, low, dense)
    return x


def pad_to(tokens: list, multiple: int = LENGTH_BLOCK) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False, dense: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: attention and selection are causal and everything else acts a
    position."""
    padded = pad_to(tokens) if len(tokens) > QUERY_BLOCK else list(tokens)
    x = hidden(config, weights, padded, low, dense)[first:len(tokens)]
    return _head(x, weights["norm_f"], weights["head"],
                 config["rms_norm_eps"], low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _gaps(x, x_ctl, norm_f, head, nxt, eps: float, low: bool):
    """Per position: how far the reference's logit of the token ``nxt``
    (the one that followed) lies below the reference's best, and the same
    for the token that the control's hidden state ``x_ctl`` puts first."""
    ref = _head(x, norm_f, head, eps, False)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    ctl = _head(x_ctl, norm_f, head, eps, low)
    return (best - ref[rows, nxt],
            best - ref[rows, jnp.argmax(ctl, axis=-1)])


def served_gaps(config: dict, weights, prompt: list, served: list,
                control: bool = False, dense_control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the float8 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode); with
    ``dense_control`` for the token that the float32 computation WITHOUT
    the selection puts first (under ``"dense"``).  The sequence is padded
    to a multiple of 512 (34,816 at most in the cell)."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    n = len(served)
    padded = pad_to(seq[:-1])
    slab = -(-n // LENGTH_BLOCK) * LENGTH_BLOCK
    at = min(first, len(padded) - slab)
    nxt = jnp.asarray(pad_to(seq[1:])[at:at + slab], jnp.int32)
    x = hidden(config, weights, padded)[at:at + slab]
    where = slice(first - at, first - at + n)
    eps = config["rms_norm_eps"]

    def against(x_ctl, low):
        got, ctl = _gaps(x, x_ctl, weights["norm_f"], weights["head"], nxt,
                         eps, low)
        return np.asarray(got)[where].tolist(), \
            np.asarray(ctl)[where].tolist()
    out = {"served": against(x, False)[0]}
    if control:
        out["control"] = against(
            hidden(config, weights, padded, True)[at:at + slab], True)[1]
    if dense_control:
        out["dense"] = against(
            hidden(config, weights, padded, False, True)[at:at + slab],
            False)[1]
    return out


# -- counts for the rooflines ------------------------------------------------
def layer_params(config: dict) -> dict:
    """Parameters of a layer by part: ``attention`` (four matrices and the
    two head norms), ``indexer`` (three matrices and the key's LayerNorm),
    ``norms`` (the block's two), ``router`` and ``expert`` (ONE routed
    expert)."""
    D = dims(config)
    d, H, G, dh = D["d"], D["H"], D["G"], D["dh"]
    return {"attention": 2 * d * H * dh + 2 * d * G * dh + 2 * dh,
            "indexer": d * D["hI"] * D["dI"] + d * D["dI"] + d * D["hI"]
            + 2 * D["dI"],
            "norms": 2 * d, "router": d * D["E"],
            "expert": 3 * d * D["fe"]}


def param_count(config: dict) -> int:
    """Parameters held: every layer with the routed experts in
    ``experts_held``, the embedding, the head and the final norm."""
    D = dims(config)
    per = layer_params(config)
    return 2 * D["V"] * D["d"] + D["d"] + D["L"] * (
        per["attention"] + per["indexer"] + per["norms"] + per["router"]
        + D["n"] * per["expert"])


def param_bytes(config: dict, experts_hit: float = 0.0,
                itemsize: int = 2) -> float:
    """Bytes of the weights one decode step has to read: everything
    outside the routed experts (the embedding table left out: a step
    gathers one row a slot) and ``experts_hit`` routed experts, summed
    over the layers."""
    D = dims(config)
    per = layer_params(config)
    held = D["L"] * D["n"] * per["expert"]
    return float(itemsize * (param_count(config) - D["V"] * D["d"] - held
                             + experts_hit * per["expert"]))


def cache_bytes(config: dict) -> dict:
    """Bytes of cache a position keeps, over all layers, by kind, in
    bfloat16, the lanes that mean something: ``kv`` = a K and a V row (4
    heads of 128 each), ``index`` = the index key's 64 lanes (the pool
    stores 128: whole lane tiles)."""
    D = dims(config)
    return {"kv": float(D["L"] * 2 * 2 * D["G"] * D["dh"]),
            "index": float(D["L"] * 2 * D["dI"])}


def sparse_attention_bytes(config: dict, live: float, selected: float
                           ) -> float:
    """Bytes one call of the sparse read (one layer) has to move: the
    index rows of the ``live`` positions it scores and the K and V rows of
    the ``selected`` positions it attends over."""
    D = dims(config)
    per = cache_bytes(config)
    return (live * per["index"] + selected * per["kv"]) / D["L"]


def decode_step_bytes(config: dict, live_positions: float,
                      experts_hit: float = None, rows_scored: float = None,
                      rows_selected: float = None) -> float:
    """Bytes one decode step needs to move.  With ONE argument it is what
    every step reads whatever the router and the selector say: the
    weights outside the routed experts and the live positions' INDEX rows
    in every layer — a floor, which the benchmark's list-less
    ``decode_roofline_pct.batch`` reads and so never over-counts (the live
    K and V rows are not in it: a step reads 2,048 of them a slot a
    layer, not all).  The fuller form takes a step's counted
    ``experts_hit`` (summed over layers), ``rows_scored`` and
    ``rows_selected`` (summed over layers and slots): the hit experts,
    the index rows scored and the K and V rows attended."""
    D = dims(config)
    per = cache_bytes(config)
    if experts_hit is None:
        return param_bytes(config) + live_positions * per["index"]
    return param_bytes(config, experts_hit) \
        + rows_scored * per["index"] / D["L"] \
        + rows_selected * per["kv"] / D["L"]


def decode_step_flops(config: dict, slots: float, pairs_routed: float,
                      rows_scored: float, rows_selected: float) -> float:
    """Operations one decode step needs: two a weight outside the routed
    experts for every slot, two a weight of an expert for every pair
    routed to it, the index scores (16 heads of 64 lanes a row scored)
    and the attention over the rows selected (32 heads, a score and a
    context of 128 lanes each); the counts summed over layers."""
    D = dims(config)
    per = layer_params(config)
    return 2.0 * slots * param_bytes(config, 0.0, itemsize=1) \
        + 2.0 * pairs_routed * per["expert"] \
        + 2.0 * rows_scored * D["hI"] * D["dI"] \
        + 2.0 * rows_selected * D["H"] * 2 * D["dh"]


def prefill_flops(config: dict, t: int) -> float:
    """Operations a prefill of ``t`` (padded) positions requires: two for
    each weight outside the routed experts at every position (the head at
    the last alone); the routed experts for the EXPECTED pairs a token a
    layer, ``k * held / router_width`` (one pair at 16 of 128 and 8 a
    token); the index scores of the causal half (16 heads of 64 lanes a
    pair); scores and context (128 lanes each, 32 heads) over the
    ``min(s + 1, topk)`` rows the ``s``-th query reads — the selected
    rows, not the causal half: a program that attends densely under a
    mask does more than is counted."""
    D = dims(config)
    per = layer_params(config)
    matrices = D["L"] * (per["attention"] + per["indexer"] + per["router"]
                         + D["k"] * D["n"] / D["E"] * per["expert"])
    k = min(t, D["topk"])
    read = k * (k + 1) / 2 + (t - k) * k
    index = float(t) * (t + 1) / 2 * D["hI"] * D["dI"]
    attention = read * D["H"] * 2 * D["dh"]
    return 2.0 * matrices * t + 2.0 * D["L"] * (index + attention) \
        + 2.0 * D["d"] * D["V"]
