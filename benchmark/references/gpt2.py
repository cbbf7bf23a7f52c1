"""Plain reference for the GPT-2 family (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners"; widths from the released
``config.json``): the full causal forward pass in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision, one sequence at a
time, no cache, no batching, one block at a time so that it fits beside
the weights alone.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed and the family's builder
(``configs/gpt2.py``) hands the same arrays to the program.

Departures of the repo's block from released GPT-2, followed here so that
the two compute the same function: the attention projections (q, k, v and
the output projection) have no bias, and are four separate matrices where
GPT-2 has one fused ``c_attn``; every matrix is drawn with std 0.02
(GPT-2 scales the residual projections by 1/sqrt(2 n_layer)).  The same as
GPT-2: pre-LayerNorm with eps 1e-5, learned absolute positions, tanh
GELU, scores scaled by 1/sqrt(head size), tied input and output
embeddings, a final LayerNorm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EPS = 1e-5
STD = 0.02


def make_weights(config: dict, key):
    """Seeded float32 weights, made on the device in one jitted call."""
    H, V, P = config["n_embd"], config["vocab_size"], config["n_positions"]
    F = config["ffn_mult"] * H
    L = config["n_layer"]

    @jax.jit
    def build(key):
        def normal(i, shape):
            return STD * jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32)
        ones = lambda n: jnp.ones((n,), jnp.float32)
        zeros = lambda n: jnp.zeros((n,), jnp.float32)
        blocks = []
        for li in range(L):
            b = 10 * (li + 1)
            blocks.append({
                "ln_1": {"g": ones(H), "b": zeros(H)},
                "attn": {"wq": normal(b, (H, H)), "wk": normal(b + 1, (H, H)),
                         "wv": normal(b + 2, (H, H)),
                         "wo": normal(b + 3, (H, H))},
                "ln_2": {"g": ones(H), "b": zeros(H)},
                "mlp": {"w_fc": normal(b + 4, (H, F)), "b_fc": zeros(F),
                        "w_proj": normal(b + 5, (F, H)), "b_proj": zeros(H)}})
        return {"wte": normal(0, (V, H)), "wpe": normal(1, (P, H)),
                "ln_f": {"g": ones(H), "b": zeros(H)}, "blocks": blocks}
    return build(key)


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + EPS) * p["g"] + p["b"]


def _mm(a, b, dtype):
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      precision=lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("n_head", "dtype"))
def _block(x, p, n_head: int, dtype):
    """One pre-LN block over one sequence ``(t, H)``.  ``dtype`` is
    float32 for the reference; the control computes everything, the
    residual stream included, in bfloat16."""
    t, H = x.shape
    d = H // n_head
    x = x.astype(dtype)
    h = _ln(x, jax.tree.map(lambda a: a.astype(dtype), p["ln_1"]))
    q, k, v = (_mm(h, p["attn"][w], dtype).reshape(t, n_head, d)
               .transpose(1, 0, 2) for w in ("wq", "wk", "wv"))
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   precision=lax.Precision.HIGHEST) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)
    ctx = jnp.einsum("hqk,hkd->hqd", a, v, precision=lax.Precision.HIGHEST)
    x = x + _mm(ctx.transpose(1, 0, 2).reshape(t, H), p["attn"]["wo"], dtype)
    h = _ln(x, jax.tree.map(lambda a: a.astype(dtype), p["ln_2"]))
    ff = jax.nn.gelu(_mm(h, p["mlp"]["w_fc"], dtype)
                     + p["mlp"]["b_fc"].astype(dtype), approximate=True)
    return x + _mm(ff, p["mlp"]["w_proj"], dtype) \
        + p["mlp"]["b_proj"].astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _embed(tokens, wte, wpe, dtype):
    return (wte[tokens] + wpe[:tokens.shape[0]]).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head(x, ln_f, wte, dtype):
    h = _ln(x, jax.tree.map(lambda a: a.astype(dtype), ln_f))
    return _mm(h, wte.T, dtype).astype(jnp.float32)


def hidden(config: dict, weights, tokens, dtype=jnp.float32):
    """The last block's output ``(len(tokens), H)`` for one sequence of
    token ids, attended causally, one block at a time."""
    x = _embed(jnp.asarray(tokens, jnp.int32), weights["wte"],
               weights["wpe"], dtype)
    for p in weights["blocks"]:
        x = _block(x, p, config["n_head"], dtype)
    return x


def logits(config: dict, weights, tokens, first: int, dtype=jnp.float32):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it."""
    return _head(hidden(config, weights, tokens, dtype)[first:],
                 weights["ln_f"], weights["wte"], dtype)


def pad_to(tokens: list, multiple: int = 128) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


@jax.jit
def _gaps(x, x_low, ln_f, wte, nxt):
    """Per position of one padded sequence: how far the reference's logit
    of the token ``nxt`` (the one that followed) lies below the
    reference's best, and the same for the token that the low-precision
    hidden state ``x_low`` puts first.  One program per padded length."""
    ref = _head(x, ln_f, wte, jnp.float32)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    low = _head(x_low, ln_f, wte, x_low.dtype)
    return (best - ref[rows, nxt],
            best - ref[rows, jnp.argmax(low, axis=-1)])


def served_gaps(config: dict, weights, prompt: list, served: list,
                control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the bfloat16 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode)."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    padded = pad_to(seq[:-1])
    nxt = jnp.asarray(pad_to(seq[1:]), jnp.int32)
    x = hidden(config, weights, padded)
    x_low = hidden(config, weights, padded, jnp.bfloat16) if control else x
    got, low = _gaps(x, x_low, weights["ln_f"], weights["wte"], nxt)
    where = slice(first, first + len(served))
    out = {"served": np.asarray(got)[where].tolist()}
    if control:
        out["control"] = np.asarray(low)[where].tolist()
    return out


def param_bytes(config: dict, itemsize: int = 4) -> float:
    """Bytes of the weights one decode step has to read: every block's
    matrices and the tied embedding as the output head (the position
    table and the embedding rows a step gathers are a few KB)."""
    H, V = config["n_embd"], config["vocab_size"]
    F = config["ffn_mult"] * H
    per_block = 4 * H * H + 2 * H * F + F + H + 4 * H
    return float(itemsize * (config["n_layer"] * per_block + V * H + 2 * H))


def kv_bytes_per_token(config: dict, itemsize: int = 4) -> float:
    return float(itemsize * 2 * config["n_layer"] * config["n_embd"])


def decode_step_bytes(config: dict, live_tokens: float,
                      itemsize: int = 4) -> float:
    """Bytes one decode step needs to move: the weights once, and the keys
    and values of the tokens that are live in the slots.  A decode step
    is bound by bytes, not by operations (8 rows against 1.5 B weights)."""
    return param_bytes(config, itemsize) \
        + live_tokens * kv_bytes_per_token(config, itemsize)
