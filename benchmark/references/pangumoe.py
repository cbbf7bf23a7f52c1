"""Plain reference for the ``pangumoe`` family: openPangu-Ultra-MoE-718B
(Pangu Ultra MoE, arXiv:2505.04519; the sandwich-norm block of Pangu
Ultra, arXiv:2504.07866; multi-head latent attention as DeepSeek-V2 gives
it, arXiv:2405.04434) as ONE CHIP'S SHARE of a deployment: it is told
which of the routed experts it holds (``experts_held``) and which slice of
the vocabulary (``vocab_size`` rows), routes over all ``router_width``
experts, and adds only its own experts' part.  The full causal forward
pass in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision, attention UNABSORBED (every key and value is formed, no
latent-space shortcut), one sequence at a time, no cache, no batching,
one layer at a time, the held experts upcast one at a time, the softmax
over blocks of 256 queries, so that 6,144 positions fit beside the held
weights.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed (bfloat16 leaves) and the family's
builder (``configs/pangumoe.py``) hands the same arrays to the program.

The equations.  ``d`` 7680, ``H`` 128 heads, ``x`` a ``(T, d)`` sequence,
RMSNorm ``x / sqrt(mean(x^2) + 1e-5) * g``.

*Attention* on ``h``::

    c_q = RMSNorm(h W_dq)                       (T, 1536)
    q   = c_q W_uq -> H heads of [q_nope 128 | q_rope 64]
    [c_kv | k_r] = h W_dkv                      (T, 512 | 64)
    c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)      one k_r for all heads
    q_rope = RoPE(q_rope)
    k_nope = c_kv W_uk, v = c_kv W_uv -> H heads of 128 each
        (the checkpoint's kv_b_proj is [W_uk | W_uv] a head; here W_uk
        is kept as a head's own (512, 128) matrix and W_uv as its
        transpose (128, 512), heads leading)
    s = (q_nope . k_nope + q_rope . k_r) / sqrt(192), causal softmax
    o = concat_h(softmax(s) v) W_o

RoPE pairs lane ``i`` with lane ``i + 32`` of the 64 and turns the pair by
``t * theta^(-i / 32)``, ``theta`` 25.6e6, ``t`` the position.

*Block* (``sandwich_norm``): ``y = x + RMSNorm_2(Attn(RMSNorm_1(x)))``,
``out = y + RMSNorm_4(FFN(RMSNorm_3(y)))``; a final RMSNorm; an untied
head over the vocabulary's slice.

*FFN*, layers before ``first_k_dense_replace``: ``W_down(silu(x W_gate) *
x W_up)``, width 18,432.  *Expert layer*, the others: ``g = sigmoid(x
W_r)`` over all 256, the 8 largest, ``w_e = g_e / (sum of the 8 + 1e-20) *
2.5``; ``out = Shared(x) + sum over chosen e in experts_held of w_e
Expert_e(x)``, each expert and the shared one a gated SiLU FFN of width
2,048.  What the experts outside ``experts_held`` would add is left out.

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
QUERY_BLOCK = 256
#: lengths are padded to a multiple of this, so a handful of programs
#: serve every request
LENGTH_BLOCK = 512
ROUTER_EPS = 1e-20


def dims(config: dict) -> dict:
    lo, hi = config["experts_held"]
    if hi - lo != config["n_routed_experts"]:
        raise ValueError("experts_held does not name n_routed_experts "
                         "experts")
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "ff": config["intermediate_size"],
            "fe": config["moe_intermediate_size"],
            "E": config["router_width"], "lo": lo, "n": hi - lo,
            "k": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "scale": config["routed_scaling_factor"],
            "L": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "V": config["vocab_size"], "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"])}


def published(config: dict) -> dict:
    """The configuration with the counts this chip's share cut put back
    as the source has them (``config["published"]``): all layers, all
    routed experts, the whole vocabulary."""
    whole = dict(config, **config["published"])
    whole["experts_held"] = [0, whole["n_routed_experts"]]
    return whole


# -- weights ---------------------------------------------------------------
def _normal(key, shape):
    return (STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dense", "Dt"))
def _make_layer(key, dense: bool, Dt: tuple):
    D = dict(Dt)
    d, H, fe = D["d"], D["H"], D["fe"]
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 20))
    normal = lambda *shape: _normal(next(keys), shape)
    ones = lambda n: jnp.ones((n,), bf)
    p = {"norm_1": ones(d), "norm_2": ones(d), "norm_3": ones(d),
         "norm_4": ones(d),
         "attn": {"w_dq": normal(d, D["rq"]), "q_norm": ones(D["rq"]),
                  "w_uq": normal(D["rq"], H * (D["nope"] + D["rope"])),
                  "w_dkv": normal(d, D["rkv"] + D["rope"]),
                  "kv_norm": ones(D["rkv"]),
                  # a head's own matrix, heads leading: W_uk (512, 128)
                  # and W_uv TRANSPOSED (128, 512)
                  "w_uk": normal(H, D["rkv"], D["nope"]),
                  "w_uv_t": normal(H, D["dv"], D["rkv"]),
                  "w_o": normal(H * D["dv"], d)}}
    if dense:
        p["mlp"] = {"w_gate": normal(d, D["ff"]), "w_up": normal(d, D["ff"]),
                    "w_down": normal(D["ff"], d)}
        return p
    # an expert's weights depend on the seed and on its index among ALL
    # the routed experts, so the sixteen shares of a layer together hold
    # the experts the uncut layer holds
    base = next(keys)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(base, e), 3)
        return _normal(kg, (d, fe)), _normal(ku, (d, fe)), \
            _normal(kd, (fe, d))
    eg, eu, ed = jax.vmap(expert)(D["lo"] + jnp.arange(D["n"]))
    p["moe"] = {"w_router": normal(d, D["E"]),
                "shared": {"w_gate": normal(d, D["shared"] * fe),
                           "w_up": normal(d, D["shared"] * fe),
                           "w_down": normal(D["shared"] * fe, d)},
                "experts": {"w_gate": eg, "w_up": eu, "w_down": ed}}
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    ke, kh = jax.random.split(key)
    return _normal(ke, (V, d)), _normal(kh, (d, V)), \
        jnp.ones((d,), jnp.bfloat16)


def make_weights(config: dict, key):
    """Seeded bfloat16 weights, made on the device, one small jitted
    program per kind of layer; of the routed experts only those in
    ``experts_held``."""
    D = dims(config)
    Dt = tuple(sorted(D.items()))
    emb, head, norm_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    layers = [_make_layer(jax.random.fold_in(key, i + 1), i < D["dense"], Dt)
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


def _gated(x, f, low):
    return _mm(jax.nn.silu(_mm(x, f["w_gate"], low)) * _mm(x, f["w_up"], low),
               f["w_down"], low)


def _attention(h, p, D, low):
    """Latent attention of one sequence as written: every position's keys
    and values formed, a block of queries at a time against every key."""
    T = h.shape[0]
    H, nope, rp, dv = D["H"], D["nope"], D["rope"], D["dv"]
    q = _mm(_rms(_mm(h, p["w_dq"], low), p["q_norm"], D["eps"]),
            p["w_uq"], low).reshape(T, H, nope + rp)
    qn, qr = q[..., :nope], rope(q[..., nope:], D["theta"])
    ckr = _mm(h, p["w_dkv"], low)
    ckv = _rms(ckr[:, :D["rkv"]], p["kv_norm"], D["eps"])
    kr = rope(ckr[:, D["rkv"]:], D["theta"])                     # (T, rp)
    # (rank, H x width) matrices out of the heads' own
    a_head = lambda w: _mm(ckv, w.reshape(D["rkv"], -1), low
                           ).reshape(T, H, -1)
    kn = a_head(p["w_uk"].transpose(1, 0, 2))
    v = a_head(p["w_uv_t"].transpose(2, 0, 1))
    B = min(T, QUERY_BLOCK)
    if T % B:
        raise ValueError(f"{T} positions are no multiple of {B}")
    keys = jnp.arange(T)[None, :]

    def block(i):
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * B, B)
        s = (jnp.einsum("qhd,khd->hqk", cut(qn), kn, precision=HI)
             + jnp.einsum("qhd,kd->hqk", cut(qr), kr, precision=HI)) \
            / math.sqrt(nope + rp)
        rows = i * B + jnp.arange(B)[:, None]
        a = jax.nn.softmax(jnp.where(keys <= rows, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HI)
    o = lax.map(block, jnp.arange(T // B)).reshape(T, H * dv)
    return _mm(o, p["w_o"], low)


def route(x, w_router, D, low=False):
    """``(chosen experts (T, k), their weights (T, k))`` over all the
    router's outputs."""
    g = jax.nn.sigmoid(_mm(x, w_router, low))
    top, idx = lax.top_k(g, D["k"])
    return idx, top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTER_EPS) \
        * D["scale"]


def routed_part(x, m, D, low=False):
    """What the held experts add for ``x (T, d)``: every held expert over
    every token, weighted by the token's weight for it (zero where the
    token did not choose it), one expert at a time."""
    idx, w = route(x, m["w_router"], D, low)

    def one(out, ew):
        e, wg, wu, wd = ew
        c = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # (T,)
        y = _gated(x, {"w_gate": wg, "w_up": wu, "w_down": wd}, low)
        return out + c[:, None] * y, None
    ex = m["experts"]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (D["lo"] + jnp.arange(D["n"]), ex["w_gate"],
                       ex["w_up"], ex["w_down"]))
    return out


def expert_layer(x, m, D, low=False):
    """``Shared(x)`` plus the held experts' part."""
    return _gated(x, m["shared"], low) + routed_part(x, m, D, low)


@functools.partial(jax.jit, static_argnames=("Dt", "low"))
def _layer(x, p, Dt: tuple, low: bool):
    """One layer over one sequence ``x (T, d)`` in float32."""
    D = dict(Dt)
    y = x + _rms(_attention(_rms(x, p["norm_1"], D["eps"]), p["attn"], D,
                            low), p["norm_2"], D["eps"])
    h = _rms(y, p["norm_3"], D["eps"])
    ff = _gated(h, p["mlp"], low) if "mlp" in p \
        else expert_layer(h, p["moe"], D, low)
    return y + _rms(ff, p["norm_4"], D["eps"])


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_f, head, eps: float, low: bool):
    return _mm(_rms(x, norm_f, eps), head, low)


def hidden(config: dict, weights, tokens, low: bool = False):
    """The last layer's output ``(len(tokens), d)`` for one sequence of
    token ids, one layer at a time."""
    Dt = tuple(sorted(dims(config).items()))
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for p in weights["layers"]:
        x = _layer(x, p, Dt, low)
    return x


def pad_to(tokens: list, multiple: int = LENGTH_BLOCK) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: attention is causal and everything else acts a position."""
    padded = pad_to(tokens) if len(tokens) > QUERY_BLOCK else list(tokens)
    x = hidden(config, weights, padded, low)[first:len(tokens)]
    return _head(x, weights["norm_f"], weights["head"],
                 config["rms_norm_eps"], low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _gaps(x, x_low, norm_f, head, nxt, eps: float, low: bool):
    """Per position: how far the reference's logit of the token ``nxt``
    (the one that followed) lies below the reference's best, and the same
    for the token that the control's hidden state ``x_low`` puts first."""
    ref = _head(x, norm_f, head, eps, False)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    ctl = _head(x_low, norm_f, head, eps, low)
    return (best - ref[rows, nxt],
            best - ref[rows, jnp.argmax(ctl, axis=-1)])


def served_gaps(config: dict, weights, prompt: list, served: list,
                control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the float8 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode).  The
    sequence is padded to a multiple of 512 (6,144 at most in the cell)."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    n = len(served)
    padded = pad_to(seq[:-1])
    slab = -(-n // LENGTH_BLOCK) * LENGTH_BLOCK
    at = min(first, len(padded) - slab)
    nxt = jnp.asarray(pad_to(seq[1:])[at:at + slab], jnp.int32)
    x = hidden(config, weights, padded)[at:at + slab]
    x_low = hidden(config, weights, padded, True)[at:at + slab] \
        if control else x
    got, low = _gaps(x, x_low, weights["norm_f"], weights["head"], nxt,
                     config["rms_norm_eps"], control)
    where = slice(first - at, first - at + n)
    out = {"served": np.asarray(got)[where].tolist()}
    if control:
        out["control"] = np.asarray(low)[where].tolist()
    return out


# -- counts for the rooflines ------------------------------------------------
def layer_params(config: dict) -> dict:
    """Parameters of a layer by part: ``attention`` (its six matrices and
    two inner norms), ``norms`` (the four of the sandwich), ``dense`` (the
    dense FFN), ``shared`` + ``router`` (what every token of an expert
    layer reads) and ``expert`` (ONE routed expert)."""
    D = dims(config)
    d, H = D["d"], D["H"]
    attention = d * D["rq"] + D["rq"] + D["rq"] * H * (D["nope"] + D["rope"]) \
        + d * (D["rkv"] + D["rope"]) + D["rkv"] \
        + D["rkv"] * H * (D["nope"] + D["dv"]) + H * D["dv"] * d
    return {"attention": attention, "norms": 4 * d, "dense": 3 * d * D["ff"],
            "shared": 3 * d * D["shared"] * D["fe"], "router": d * D["E"],
            "expert": 3 * d * D["fe"]}


def param_count(config: dict) -> int:
    """Parameters held: every layer with the routed experts in
    ``experts_held``, the embedding and the head over ``vocab_size`` rows,
    the final norm."""
    D = dims(config)
    per = layer_params(config)
    outside = per["attention"] + per["norms"]
    return 2 * D["V"] * D["d"] + D["d"] \
        + D["dense"] * (outside + per["dense"]) \
        + (D["L"] - D["dense"]) * (outside + per["shared"] + per["router"]
                                   + D["n"] * per["expert"])


def param_bytes(config: dict, experts_hit: float = 0.0,
                itemsize: int = 2) -> float:
    """Bytes of the weights one decode step has to read: everything
    outside the routed experts (the embedding table left out: a step
    gathers one row a slot) and ``experts_hit`` routed experts, summed
    over the layers."""
    D = dims(config)
    per = layer_params(config)
    held = (D["L"] - D["dense"]) * D["n"] * per["expert"]
    return float(itemsize * (param_count(config) - D["V"] * D["d"] - held
                             + experts_hit * per["expert"]))


def cache_bytes(config: dict) -> dict:
    """Bytes of cache a live position needs read: ``paged`` = the latent
    row ``[c_kv | k_r]`` of every layer in bfloat16, the 576 lanes that
    mean something (the pool stores 640: whole lane tiles)."""
    D = dims(config)
    return {"paged": float(D["L"] * 2 * (D["rkv"] + D["rope"]))}


def decode_step_bytes(config: dict, live_positions: float,
                      experts_hit: float = 0.0) -> float:
    """Bytes one decode step needs to move: the weights outside the
    routed experts, ``experts_hit`` routed experts (summed over layers:
    the counter's, not all that are held) and the live latent rows.  With
    one argument it is what EVERY step reads whatever the router says —
    a floor under the step's bytes, which the benchmark's list-less
    ``decode_roofline_pct.batch`` reads and so never over-counts."""
    return param_bytes(config, experts_hit) \
        + live_positions * cache_bytes(config)["paged"]


def latent_attention_bytes(config: dict, live_positions: float) -> float:
    """Bytes one call of the latent attention kernel (one layer) has to
    read: the live rows' 576 bfloat16 lanes."""
    return live_positions * cache_bytes(config)["paged"] \
        / config["num_hidden_layers"]


def latent_attention_flops(config: dict, live_positions: float) -> float:
    """Operations of one call: for every head and live row, a score over
    576 lanes and a context over 512."""
    D = dims(config)
    return 2.0 * live_positions * D["H"] * (2 * D["rkv"] + D["rope"])


def decode_step_flops(config: dict, live_positions: float, slots: float,
                      pairs_routed: float = 0.0) -> float:
    """Operations one decode step needs: two a weight outside the routed
    experts for every slot, two a weight of an expert for every pair
    routed to it (summed over layers), and the absorbed attention over
    the live rows in every layer."""
    D = dims(config)
    per = layer_params(config)
    outside = param_bytes(config, 0.0, itemsize=1)
    return 2.0 * slots * outside + 2.0 * pairs_routed * per["expert"] \
        + D["L"] * latent_attention_flops(config, live_positions)


def prefill_flops(config: dict, t: int) -> float:
    """Operations a prefill of ``t`` (padded) positions requires: two for
    each weight outside the routed experts at every position (the head
    at the last alone); the routed experts for the EXPECTED pairs a
    token a layer, ``k * held / router_width`` (half a pair at 16 of 256
    and 8 a token), whatever form the program computes them in; the
    causal half of the unabsorbed scores (192 lanes a head) and context
    (128)."""
    D = dims(config)
    per = layer_params(config)
    nE = D["L"] - D["dense"]
    matrices = D["L"] * per["attention"] + D["dense"] * per["dense"] \
        + nE * (per["shared"] + per["router"]
                + D["k"] * D["n"] / D["E"] * per["expert"])
    attention = D["L"] * float(t) * t * D["H"] * (D["nope"] + D["rope"]
                                                  + D["dv"])
    return 2.0 * matrices * t + attention + 2.0 * D["d"] * D["V"]
