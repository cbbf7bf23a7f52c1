"""Plain reference for the ``nemotronh`` family: NVIDIA-Nemotron-3-Super
(``model_type`` ``nemotron_h``): Mamba-2 blocks (SSD, arXiv:2405.21060),
attention blocks and LatentMoE expert blocks (Nemotron-H,
arXiv:2504.03624; the LatentMoE of the Nemotron 3 description) in the
order a pattern string gives, as ONE CHIP'S SHARE of a deployment: it is
told which of the routed experts it holds (``experts_held``), which slice
of the vocabulary (``vocab_size`` rows) and which published blocks
(``first_block`` on), routes over all ``router_width`` experts, and adds
only its own experts' part.  The full causal forward pass in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision,
one sequence at a time, no cache, no batching, one block at a time; **the
SSD is the recurrence below, position by position**, a sequential
``lax.scan`` (so the program's chunked form is compared with the
definition and not with itself), attention over blocks of 256 queries
and every held expert over every token, one expert at a time, so that
5,120 positions fit beside the held weights.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed and the family's builder
(``configs/nemotronh.py``) hands the same arrays to the program.

The equations.  ``d`` 4096, ``x`` a ``(T, d)`` sequence, RMSNorm ``x /
sqrt(mean(x^2) + 1e-5) * g``.

*Stack*: block ``i`` is ``x = x + Sub_i(RMSNorm_i(x))``, ``Sub_i`` by
character ``i`` of ``hybrid_override_pattern``: ``M`` Mamba-2, ``*``
attention, ``E`` expert layer; ONE sub-layer a block.  A final RMSNorm, an
untied head over the vocabulary's slice.  No positions are added
anywhere.

*Mamba-2* on ``h`` (``H`` 128 heads of ``P`` 64, ``d_in = H P`` 8,192,
``G`` 8 groups, ``N`` 128, ``K`` 4)::

    [z (d_in) | xBC (d_in + 2 G N) | dt (H)] = h W_in          no bias
    xBC_t = silu(b_conv + sum_k w_k * xBC_{t-K+1+k})   depthwise, tap K-1
                          meets the position itself, zeros before the sequence
    xBC = [x (H, P) | B (G, N) | C (G, N)]
    dt = softplus(dt + dt_bias) a head;  A = -exp(A_log) a head (a scalar)
    per head h, S in R^{P x N}, zero at the start, g(h) = h // (H / G):
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{g(h),t}^T
        y_t = S_t C_{g(h),t} + D_h x_t
    y = RMSNorm_group(y * silu(z)): the gate FIRST, then an RMS norm over
        each of the G groups of d_in / G channels, a gain a channel
    out = y W_out

*Attention* (32 query heads on 2 KV heads of 128, no bias, NO rotary)::

    q, k, v = h W_q, h W_k, h W_v;  s = q . k / sqrt(128), causal softmax,
    query head j on KV head j // 16;  out = concat_h(softmax(s) v) W_o

*Expert layer* (LatentMoE): ``s = sigmoid(h W_r)`` over all 512 in
float32; the choice is the 22 largest of ``s + b`` (``b`` the correction
bias, in the choice only; ``n_group`` 1 and ``topk_group`` 1 make the
group stage the whole set); ``w = s_chosen / sum(s_chosen) x 5``; ``u = h
W_down`` (4096 -> 1024); ``r = sum over chosen e in experts_held of w_e
W2_e relu(W1_e u)^2``; ``FFN(h) = r W_up (1024 -> 4096) + W2_s relu(W1_s
h)^2``, the shared expert at FULL width.  What the experts outside
``experts_held`` would add is left out.

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


def dims(config: dict) -> dict:
    lo, hi = config["experts_held"]
    pattern = config["hybrid_override_pattern"]
    if hi - lo != config["n_routed_experts"]:
        raise ValueError("experts_held does not name n_routed_experts "
                         "experts")
    if len(pattern) != config["num_hidden_layers"] \
            or set(pattern) - set("M*E"):
        raise ValueError("hybrid_override_pattern does not name "
                         "num_hidden_layers blocks of M, * and E")
    if config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["n_shared_experts"] != 1:
        raise ValueError("the reference has one router group and one "
                         "shared expert")
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    if H * P != config["expand"] * config["hidden_size"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    return {"d": config["hidden_size"], "H": H, "P": P,
            "G": config["n_groups"], "N": config["ssm_state_size"],
            "K": config["conv_kernel"],
            "AH": config["num_attention_heads"],
            "KV": config["num_key_value_heads"], "dh": config["head_dim"],
            "fe": config["moe_intermediate_size"],
            "lat": config["moe_latent_size"],
            "fs": config["moe_shared_expert_intermediate_size"],
            "E": config["router_width"], "lo": lo, "n": hi - lo,
            "k": config["num_experts_per_tok"],
            "scale": float(config["routed_scaling_factor"]),
            "first": config["first_block"], "pattern": pattern,
            "V": config["vocab_size"], "eps": config["layer_norm_epsilon"]}


def published(config: dict) -> dict:
    """The configuration with what this chip's share cut put back as the
    source has it (``config["published"]``): all blocks from the first,
    all routed experts, the whole vocabulary; the prediction module stays
    out (``param_count`` does not count it)."""
    whole = dict(config, **config["published"])
    whole["experts_held"] = [0, whole["n_routed_experts"]]
    whole["first_block"] = 0
    return whole


def experts_held(config: dict) -> int:
    """Routed experts this chip holds in each expert layer."""
    return config["n_routed_experts"]


def expert_layers(config: dict) -> int:
    return config["hybrid_override_pattern"].count("E")


# -- weights ---------------------------------------------------------------
def _normal(key, shape, dtype=jnp.bfloat16):
    return (STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("kind", "Dt"))
def _make_block(key, kind: str, Dt: tuple):
    D = dict(Dt)
    d, H, P, G, N, K = D["d"], D["H"], D["P"], D["G"], D["N"], D["K"]
    bf, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(key, 16))
    normal = lambda *shape: _normal(next(keys), shape)

    def uniform(shape, lo, hi, dtype=bf):
        return jax.random.uniform(next(keys), shape, f32, lo, hi
                                  ).astype(dtype)
    p = {"norm": jnp.ones((d,), bf)}
    if kind == "M":
        cw = H * P + 2 * G * N
        # the family's initialiser (assumed.mamba_draw): dt ~ logU[time_step
        # _min, _max] floored, dt_bias its inverse softplus; A ~ U[1, 16];
        # D = 1; the three small vectors float32 as the family keeps them
        dt = jnp.maximum(jnp.exp(uniform((H,), math.log(D["dt_min"]),
                                         math.log(D["dt_max"]), f32)),
                         D["dt_floor"])
        p["mamba"] = {
            "w_in": normal(d, 2 * H * P + 2 * G * N + H),
            # as torch draws a depthwise Conv1d: +-K^-0.5, weight and bias
            "conv_w": uniform((K, cw), -K ** -0.5, K ** -0.5),
            "conv_b": uniform((cw,), -K ** -0.5, K ** -0.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(uniform((H,), 1.0, 16.0, f32)),
            "d": jnp.ones((H,), f32),
            "g_norm": jnp.ones((H * P,), bf),
            "w_out": normal(H * P, d)}
    elif kind == "*":
        p["attn"] = {"w_q": normal(d, D["AH"] * D["dh"]),
                     "w_k": normal(d, D["KV"] * D["dh"]),
                     "w_v": normal(d, D["KV"] * D["dh"]),
                     "w_o": normal(D["AH"] * D["dh"], d)}
    elif kind == "E":
        # an expert's weights depend on the seed and on its index among
        # ALL the routed experts, so the four shares of a layer together
        # hold the experts the uncut layer holds
        base = next(keys)

        def expert(e):
            k1, k2 = jax.random.split(jax.random.fold_in(base, e))
            return _normal(k1, (D["lat"], D["fe"])), \
                _normal(k2, (D["fe"], D["lat"]))
        w1, w2 = jax.vmap(expert)(D["lo"] + jnp.arange(D["n"]))
        p["moe"] = {
            # the router in float32, as the family keeps it
            "w_router": _normal(next(keys), (d, D["E"]), f32),
            # the correction bias, NOT zero, and small (assumed.router)
            "bias": uniform((D["E"],), -0.01, 0.01, f32),
            "w_down": normal(d, D["lat"]), "w_up": normal(D["lat"], d),
            "shared": {"w_1": normal(d, D["fs"]), "w_2": normal(D["fs"], d)},
            "experts": {"w_1": w1, "w_2": w2}}
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    ke, kh = jax.random.split(key)
    return _normal(ke, (V, d)), _normal(kh, (d, V)), \
        jnp.ones((d,), jnp.bfloat16)


def _static(config: dict) -> tuple:
    D = dims(config)
    D.update(dt_min=config["time_step_min"], dt_max=config["time_step_max"],
             dt_floor=config["time_step_floor"])
    return tuple(sorted(D.items()))


def make_weights(config: dict, key):
    """Seeded weights, made on the device, one small jitted program per
    kind of block; of the routed experts only those in ``experts_held``.
    A block's weights depend on the seed and on its PUBLISHED index."""
    D = dims(config)
    Dt = _static(config)
    emb, head, norm_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    return {"emb": emb, "head": head, "norm_f": norm_f,
            "layers": [_make_block(jax.random.fold_in(key, D["first"] + j + 1),
                                   kind, Dt)
                       for j, kind in enumerate(D["pattern"])]}


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


def relu2(u):
    return jnp.square(jax.nn.relu(u))


def _conv(x, w):
    """Depthwise causal convolution of ``x (T, c)`` with ``w (K, c)``."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), jnp.float32), x])
    w = w.astype(jnp.float32)
    return sum(w[j] * xp[j:j + T] for j in range(K))


def ssd_recurrence(x, dt, A, B, C):
    """The recurrence itself, position by position: ``x (T, H, P)``, ``dt
    (T, H)``, ``A (H,)``, ``B, C (T, G, N)``, head ``h`` on group ``h //
    (H / G)``; returns ``(y (T, H, P)`` before the ``D`` skip, ``S_T (H,
    P, N))``."""
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    of_head = lambda a: jnp.repeat(a, H // G, axis=0)            # (H, N)

    def step(S, t):
        xt, dtt, Bt, Ct = t
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * of_head(Bt)[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, of_head(Ct), precision=HI)
    S, y = lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                    (x, dt, B, C))
    return y, S


def mamba_inputs(h, p, D, low=False):
    """``(z (T, d_in), x (T, H, P), B, C (T, G, N), dt (T, H))`` of one
    sequence: the projection, the convolution with its bias and SiLU,
    ``dt`` through its bias and softplus."""
    T = h.shape[0]
    H, P, G, N = D["H"], D["P"], D["G"], D["N"]
    dIn, gn = H * P, G * N
    f32 = lambda a: a.astype(jnp.float32)
    zxd = _mm(h, p["w_in"], low)
    xBC = jax.nn.silu(_conv(zxd[:, dIn:2 * dIn + 2 * gn], p["conv_w"])
                      + f32(p["conv_b"]))
    return (zxd[:, :dIn], xBC[:, :dIn].reshape(T, H, P),
            xBC[:, dIn:dIn + gn].reshape(T, G, N),
            xBC[:, dIn + gn:].reshape(T, G, N),
            jax.nn.softplus(zxd[:, 2 * dIn + 2 * gn:] + f32(p["dt_bias"])))


def _mamba(h, p, D, low):
    """The Mamba-2 mixer over one sequence ``h (T, d)``."""
    T = h.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    z, x, B, C, dt = mamba_inputs(h, p, D, low)
    y, _ = ssd_recurrence(x, dt, -jnp.exp(f32(p["a_log"])), B, C)
    y = (y + f32(p["d"])[:, None] * x).reshape(T, -1) * jax.nn.silu(z)
    g = y.reshape(T, D["G"], -1)                  # the gate first, then the
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + D["eps"])
    return _mm(g.reshape(T, -1) * f32(p["g_norm"]), p["w_out"], low)


def _attention(h, p, D, low):
    """Grouped-query attention of one sequence, no positions: a block of
    queries at a time against every key."""
    T = h.shape[0]
    AH, KV, dh = D["AH"], D["KV"], D["dh"]
    q = _mm(h, p["w_q"], low).reshape(T, KV, AH // KV, dh)
    k = _mm(h, p["w_k"], low).reshape(T, KV, dh)
    v = _mm(h, p["w_v"], low).reshape(T, KV, dh)
    B = min(T, QUERY_BLOCK)
    if T % B:
        raise ValueError(f"{T} positions are no multiple of {B}")
    keys = jnp.arange(T)[None, :]

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * B, B)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=HI) / math.sqrt(dh)
        rows = i * B + jnp.arange(B)[:, None]
        a = jax.nn.softmax(jnp.where(keys <= rows, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", a, v, precision=HI)
    o = lax.map(block, jnp.arange(T // B)).reshape(T, AH * dh)
    return _mm(o, p["w_o"], low)


def route(x, m, D, low=False):
    """``(chosen experts (T, k), their weights (T, k))`` over all the
    router's outputs: the bias in the choice and not in the weight."""
    s = jax.nn.sigmoid(_mm(x, m["w_router"], low))                # (T, E)
    idx = lax.top_k(s + m["bias"].astype(jnp.float32), D["k"])[1]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * D["scale"]


def routed_part(x, m, D, low=False):
    """What the held experts add IN THE LATENT for ``x (T, d)``: every
    held expert over every token's ``u = x W_down``, weighted by the
    token's weight for it (zero where the token did not choose it), one
    expert at a time."""
    idx, w = route(x, m, D, low)
    u = _mm(x, m["w_down"], low)

    def one(out, ew):
        e, w1, w2 = ew
        c = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # (T,)
        return out + c[:, None] * _mm(relu2(_mm(u, w1, low)), w2, low), None
    ex = m["experts"]
    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (D["lo"] + jnp.arange(D["n"]), ex["w_1"], ex["w_2"]))
    return out


def shared_part(x, m, low=False):
    return _mm(relu2(_mm(x, m["shared"]["w_1"], low)), m["shared"]["w_2"],
               low)


def expert_layer(x, m, D, low=False):
    """``Shared(x)`` plus the held experts' part through ``W_up``."""
    return shared_part(x, m, low) \
        + _mm(routed_part(x, m, D, low), m["w_up"], low)


@functools.partial(jax.jit, static_argnames=("Dt", "low"))
def _block(x, p, Dt: tuple, low: bool):
    """One block over one sequence ``x (T, d)`` in float32."""
    D = dict(Dt)
    h = _rms(x, p["norm"], D["eps"])
    if "mamba" in p:
        return x + _mamba(h, p["mamba"], D, low)
    if "attn" in p:
        return x + _attention(h, p["attn"], D, low)
    return x + expert_layer(h, p["moe"], D, low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_f, head, eps: float, low: bool):
    return _mm(_rms(x, norm_f, eps), head, low)


def hidden(config: dict, weights, tokens, low: bool = False):
    """The last block's output ``(len(tokens), d)`` for one sequence of
    token ids, one block at a time."""
    Dt = tuple(sorted(dims(config).items()))
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for p in weights["layers"]:
        x = _block(x, p, Dt, low)
    return x


def pad_to(tokens: list, multiple: int = LENGTH_BLOCK) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: every mixer is causal and everything else acts a position."""
    padded = pad_to(tokens) if len(tokens) > QUERY_BLOCK else list(tokens)
    x = hidden(config, weights, padded, low)[first:len(tokens)]
    return _head(x, weights["norm_f"], weights["head"],
                 config["layer_norm_epsilon"], low)


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
                control: bool = False, dense_control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the float8 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode).  The
    sequence is padded to a multiple of 512 (5,120 at most in the cell).
    ``dense_control`` is the cell's driver asking for a family's second
    control: this family has none, and the keyword changes nothing."""
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
                     config["layer_norm_epsilon"], control)
    where = slice(first - at, first - at + n)
    out = {"served": np.asarray(got)[where].tolist()}
    if control:
        out["control"] = np.asarray(low)[where].tolist()
    return out


# -- counts for the rooflines ------------------------------------------------
def block_params(config: dict) -> dict:
    """Parameters of a block by part: ``M`` (the Mamba-2 mixer's two
    matrices with its small vectors), ``*`` (attention's four), ``norm``
    (a block's one), and of an expert block ``router`` (with its bias),
    ``latent`` (both projections), ``shared`` and ``expert`` (ONE routed
    expert)."""
    D = dims(config)
    d, H, P, G, N, K = D["d"], D["H"], D["P"], D["G"], D["N"], D["K"]
    cw = H * P + 2 * G * N
    return {"M": d * (H * P + cw + H) + K * cw + cw + 3 * H + H * P
            + H * P * d,
            "*": 2 * d * D["AH"] * D["dh"] + 2 * d * D["KV"] * D["dh"],
            "norm": d, "router": d * D["E"] + D["E"],
            "latent": 2 * d * D["lat"], "shared": 2 * d * D["fs"],
            "expert": 2 * D["lat"] * D["fe"]}


def param_count(config: dict) -> int:
    """Parameters held: every block with the routed experts in
    ``experts_held``, the embedding and the head over ``vocab_size`` rows,
    the final norm."""
    D = dims(config)
    per = block_params(config)
    n = {kind: D["pattern"].count(kind) for kind in "M*E"}
    return 2 * D["V"] * D["d"] + D["d"] + len(D["pattern"]) * per["norm"] \
        + n["M"] * per["M"] + n["*"] * per["*"] \
        + n["E"] * (per["router"] + per["latent"] + per["shared"]
                    + D["n"] * per["expert"])


def expert_bytes(config: dict, experts: float = 1.0) -> float:
    """Bytes of ``experts`` routed experts' two matrices (bfloat16)."""
    return 2.0 * experts * block_params(config)["expert"]


def param_bytes(config: dict, experts_hit: float = 0.0) -> float:
    """Bytes of the weights one decode step has to read: everything
    outside the routed experts (the embedding table left out: a step
    gathers one row a slot; the routers in float32, the rest bfloat16)
    and ``experts_hit`` routed experts, summed over the layers."""
    D = dims(config)
    per = block_params(config)
    nE = D["pattern"].count("E")
    rest = param_count(config) - D["V"] * D["d"] \
        - nE * (D["n"] * per["expert"] + per["router"])
    return 2.0 * rest + 4.0 * nE * per["router"] \
        + expert_bytes(config, experts_hit)


def cache_bytes(config: dict) -> dict:
    """Bytes of each kind of state the blocks keep between steps:
    ``paged`` per live position (K and V rows of every attention block,
    bfloat16), ``state`` per live slot (every Mamba-2 block's float32 ``(H,
    P, N)`` state) and ``window`` per live slot (its convolution's ``K -
    1`` bfloat16 rows)."""
    D = dims(config)
    nM, nA = D["pattern"].count("M"), D["pattern"].count("*")
    cw = D["H"] * D["P"] + 2 * D["G"] * D["N"]
    return {"paged": float(nA * 2 * 2 * D["KV"] * D["dh"]),
            "state": float(nM * 4 * D["H"] * D["P"] * D["N"]),
            "window": float(nM * 2 * (D["K"] - 1) * cw)}


def ssd_state_bytes(config: dict, live_slots: float) -> float:
    """Bytes the Mamba-2 blocks' matrix state costs one decode step:
    every live slot's states read once and written once."""
    return 2.0 * live_slots * cache_bytes(config)["state"]


def decode_step_bytes(config: dict, live_positions: float,
                      experts_hit: float = 0.0,
                      live_slots: float = 0.0) -> float:
    """Bytes one decode step needs to move: the weights outside the
    routed experts, ``experts_hit`` routed experts (summed over layers:
    the counter's, not all that are held), the live K/V rows, and the
    live slots' states and windows read and written.  With one argument
    it is what EVERY step reads whatever the router says and however many
    slots are live — a floor under the step's bytes, which the
    benchmark's list-less ``decode_roofline_pct.batch`` reads and so
    never over-counts."""
    c = cache_bytes(config)
    return param_bytes(config, experts_hit) + live_positions * c["paged"] \
        + 2.0 * live_slots * (c["state"] + c["window"])


def prefill_flops(config: dict, t: int) -> float:
    """Operations a prefill of ``t`` (padded) positions requires: two for
    each weight outside the routed experts at every position (the head at
    the last alone); the routed experts for the EXPECTED pairs a token a
    layer, ``k * held / router_width`` (5.5 at 128 of 512 and 22 a
    token), whatever form the program computes them in; the causal half
    of the attention blocks' scores and context; the SSD as its
    recurrence counts it (decay, the rank-one update, the read against
    ``C``: 5 a state element a position), whatever form the program
    computes it in."""
    D = dims(config)
    per = block_params(config)
    n = {kind: D["pattern"].count(kind) for kind in "M*E"}
    d, H, P = D["d"], D["H"], D["P"]
    cw = H * P + 2 * D["G"] * D["N"]
    matrices = n["M"] * (d * (H * P + cw + H) + H * P * d) \
        + n["*"] * per["*"] \
        + n["E"] * (d * D["E"] + per["latent"] + per["shared"]
                    + D["k"] * D["n"] / D["E"] * per["expert"])
    attention = n["*"] * float(t) * t * D["AH"] * 2 * D["dh"]
    return 2.0 * matrices * t + attention \
        + n["M"] * 5.0 * H * P * D["N"] * t + 2.0 * d * D["V"]
