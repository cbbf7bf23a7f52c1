"""The Pangu-Ultra-MoE served LM (``nlp/pangu_moe.py``: latent attention
with rotary positions, a shared expert beside routed ones of which a chip
holds a share, sandwich norm) against the benchmark's plain reference, at
a small size on the CPU: the latent kernel against its gathered form, the
expert layer's shares against the uncut layer, the full forward on
logits, then prefill (unabsorbed) + decode (absorbed) through the
scheduler's pool of latent rows.

The reference is ``benchmark/references/pangumoe.py`` itself, loaded by
path: it imports nothing of the program, so the benchmark stays
independent of what it is compared with.
"""
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.cbatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one dense layer and two expert layers; this "chip" holds experts 4..7 of
# 16 and the router chooses 4 a token: one held pair a token on average
TINY = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "router_width": 16,
        "experts_held": [4, 8], "n_routed_experts": 4,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "sandwich_norm": True, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "vocab_size": 96, "rms_norm_eps": 1e-5,
        "rope_theta": 25.6e6}
PAGE, SLOTS, CAP = 4, 3, 64
EXPERT_LAYERS = 2

# float32 weights on the CPU: both sides compute in float32 and differ in
# the order of their sums and in the form of the attention (the step
# folds W_uk into the query and W_uv behind the context, the reference
# forms every key and value); measured 3.3e-7 on logits whose spread is
# 1.07: an ulp or two after 3 layers of four norms each
TOL_F32 = 5e-6
# bfloat16 weights: the program rounds the residual stream, the latent
# rows, the queries and softmax weights and every matmul's input to 8
# bits of mantissa where the reference keeps float32.  Held on the MEAN
# error over positions and vocabulary: measured 0.0010 (forward) and
# 0.0014-0.0019 (paged decode), where float8 inputs and weights read
# 0.023.  The largest error does not tell the two apart: now and then
# the rounded stream chooses another fourth expert than the float32 one
# (the two candidates' scores lie closer than the rounding) and that
# position's logits move by up to 0.09 (measured 0.083, 0.093; forward
# 0.005), where float8's largest is 0.12-0.19; it is held loosely
TOL_BF16_MEAN, TOL_BF16_MAX = 0.006, 0.3


def _load(rel, name):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/references/pangumoe.py", "bench_ref_pangumoe")


@pytest.fixture(scope="module")
def family():
    return _load("benchmark/configs/pangumoe.py", "bench_cfg_pangumoe")


@pytest.fixture(scope="module")
def weights(ref):
    import jax
    return ref.make_weights(TINY, jax.random.PRNGKey(3))


def _as(weights, dtype):
    import jax
    return jax.tree.map(lambda a: a.astype(dtype), weights)


def _lm(family, weights, dtype):
    return family.build_lm(dict(TINY, dtype=dtype), _as(weights, dtype), CAP)


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, TINY["vocab_size"], size=n).tolist()
            for n in lengths]


# -- the latent rows: their spec, their pool, their kernel --------------------
def test_cache_spec_names_one_latent_row_and_the_pool_holds_one_array(
        family, weights):
    """A latent row is ``latentWidth + ropeWidth`` lanes stored in whole
    lane tiles, in ONE pool: there is no V.  The pool's bytes, its
    sharding's unit and the routing counters' slot state all follow from
    the spec."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import CacheSpec
    from deeplearning4j_tpu.remote import KVCachePool
    spec = _lm(family, weights, "bfloat16").cacheSpec()
    assert (spec.pagedLayers, spec.latentWidth, spec.ropeWidth) == (3, 32, 8)
    assert (spec.rowWidth, spec.pagedPools, spec.splitHeads) == (128, 1, 1)
    pool = KVCachePool.forSpec(spec, PAGE, 9, SLOTS, 4)
    assert [(a.shape, a.dtype) for a in pool.arrays] == [
        ((3, 9, PAGE, 128), jnp.bfloat16), ((1, SLOTS, 3), jnp.int32)]
    assert pool.pageBytes == 3 * PAGE * 128 * 2
    # 512 + 64 lanes are stored as 640; keys and values as before
    assert CacheSpec(5, 1, 576, latentWidth=512, ropeWidth=64
                     ).rowWidth == 640
    kv = CacheSpec(2, 25, 64)
    assert (kv.rowWidth, kv.pagedPools, kv.splitHeads) == (1600, 2, 25)
    assert len(KVCachePool.forSpec(kv, PAGE, 9, SLOTS, 4).arrays) == 2


@pytest.mark.parametrize("tq", [1, 2])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_latent_kernel_matches_the_gathered_form(dtype, tol, tq):
    """``_attend_latent_pages`` (interpreted) against
    ``_attend_latent_gathered``: ragged slots, a left pad that swallows
    whole pages, pages in no order, chunks of 2, 8 and all of a slot's
    pages.  A float32 pool enters the matmuls in three bfloat16 pieces
    an operand (exact to float32 rounding); a bfloat16 pool's softmax
    weights are rounded to bfloat16 before the context's matmul where the
    gathered form rounds them after normalising: 3e-3 of values of size 4."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    rs = np.random.RandomState(0)
    S, h, W, vw, ps, P, L, npg = 3, 4, 256, 128, 4, 40, 2, 64
    pool = jnp.asarray(rs.randn(L, npg, ps, W), dtype)
    pt = np.zeros((S, P), np.int32)
    ids = rs.permutation(np.arange(1, npg))[:S * 20].reshape(S, 20)
    pt[:, :20] = ids
    pos = jnp.asarray([5, 70, 33], jnp.int32)
    start = jnp.asarray([0, 9, 30], jnp.int32)
    q = jnp.asarray(0.2 * rs.randn(S, h, tq, W), dtype)
    want = A._attend_latent_gathered(q, pool, jnp.asarray(pt), pos, start,
                                     li=1, valueWidth=vw)
    kept = A._LATENT_CHUNK_ROWS
    try:
        for rows in (8, 32, 512):
            A._LATENT_CHUNK_ROWS = rows
            got = A._attend_latent_pages(q, pool, jnp.asarray(pt), pos,
                                         start, li=1, valueWidth=vw,
                                         interpret=True)
            assert got.shape == (S, h, tq, vw)
            assert float(jnp.abs(got - want).max()) < tol
    finally:
        A._LATENT_CHUNK_ROWS = kept


# -- the expert layer that holds a share --------------------------------------
def _expert_layer_inputs(ref, config, T, seed=0):
    """An expert layer's weights at ``config`` and an input ``(T, d)``."""
    import jax
    D = ref.dims(config)
    m = ref._make_layer(jax.random.PRNGKey(seed), False,
                        tuple(sorted(D.items())))["moe"]
    # ten times the seed's N(0, 0.02): outputs of size 1, a decisive router
    m = jax.tree.map(lambda a: 10.0 * a.astype("float32"), m)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, D["d"]),
                          "float32")
    return D, m, x


def _share(form, x, m, D, real=None):
    """The program's routed part for the share ``D`` describes."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    idx, w = moe.route_sigmoid_topk(x, m["w_router"], D["k"], D["scale"])
    ex = (m["experts"]["w_gate"], m["experts"]["w_up"],
          m["experts"]["w_down"], D["lo"])
    if form == "dense":
        return moe.moe_share_dense(x, idx, w, *ex), idx
    real = jnp.ones((x.shape[0],), bool) if real is None else real
    return moe.moe_share_grouped(x, idx, w, *ex, real), idx


@pytest.mark.parametrize("form", ["dense", "grouped"])
@pytest.mark.parametrize("experts,shares", [(256, 16), (8, 4)])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(ref, form, experts,
                                                         shares):
    """The routed parts that all the shares give (``experts_held = (n r,
    n r + n)``, every share's experts drawn by their index among ALL),
    with the shared expert counted once, equal what the reference gives
    for the uncut layer: 16 shares of 16 of 256 experts at 8 a token, and
    4 shares of 2 of 8 at 3.  Float32; the sums differ in order (measured
    2e-6 and 5e-6 at outputs of size 11 and 15)."""
    n = experts // shares
    base = dict(TINY, router_width=experts, n_routed_experts=n,
                num_experts_per_tok=8 if experts == 256 else 3)
    whole = dict(base, n_routed_experts=experts, experts_held=[0, experts])
    Dw, mw, x = _expert_layer_inputs(ref, whole, T=24)
    want = np.asarray(ref.expert_layer(x, mw, Dw))
    total = np.asarray(ref._gated(x, mw["shared"], False))
    for r in range(shares):
        D, m, _ = _expert_layer_inputs(
            ref, dict(base, experts_held=[n * r, n * r + n]), T=24)
        np.testing.assert_array_equal(np.asarray(m["w_router"]),
                                      np.asarray(mw["w_router"]))
        np.testing.assert_array_equal(
            np.asarray(m["experts"]["w_up"]),
            np.asarray(mw["experts"]["w_up"][n * r:n * r + n]))
        part, _ = _share(form, x, m, D)
        # and the reference, given the same share, gives the same part
        assert np.abs(np.asarray(part) - np.asarray(
            ref.routed_part(x, m, D))).max() < 5e-5
        total = total + np.asarray(part)
    assert np.abs(want).max() > 1.0
    assert np.abs(total - want).max() < 5e-5


@pytest.mark.parametrize("case", ["one_held_expert", "all_chosen_held",
                                  "random_left_padded"])
def test_grouped_matmul_is_the_dense_and_masked_form_and_drops_nothing(
        ref, case):
    """``moe_share_grouped`` (pairs sorted by expert, ``lax.ragged_dot``
    by group, ``T`` rows a pass) against ``moe_share_dense`` (every held
    expert over every token) and the reference's share, with a router
    skewed so that EVERY token chooses held expert 5 (its group is all
    ``T`` tokens: a capacity factor would have dropped most), with one
    whose four choices are all held (``4 T`` pairs: four passes), and
    with a random one over a left-padded sequence whose pads route
    nothing.  The counts say where every pair went."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    T = 24
    D, m, x = _expert_layer_inputs(ref, TINY, T)
    real = jnp.ones((T,), bool)
    if case == "one_held_expert":
        m["w_router"] = m["w_router"].at[:, 5].set(0.0) \
            + 50.0 * jnp.eye(16)[5] * jnp.sign(x[:1].T)
        x = jnp.abs(x[:1]) * jnp.sign(x[:1]) * jnp.ones((T, 1)) \
            + 0.01 * x
    elif case == "all_chosen_held":
        boost = jnp.zeros((16,)).at[4:8].set(1.0)
        m["w_router"] = 0.0 * m["w_router"] + 50.0 * boost * jnp.sign(
            x[:1].T)
        x = x[:1] * jnp.ones((T, 1)) + 0.01 * x
    else:
        real = jnp.arange(T) >= 7
    dense, idx = _share("dense", x, m, D)
    grouped, _ = _share("grouped", x, m, D, real)
    want = ref.routed_part(x, m, D)
    keep = np.asarray(real)
    assert np.abs(np.asarray(dense) - np.asarray(want)).max() < 5e-5
    assert np.abs(np.asarray(grouped)[keep]
                  - np.asarray(want)[keep]).max() < 5e-5
    assert not np.asarray(grouped)[~keep].any()
    routed, absent, hit = (int(n) for n in moe.moe_share_counts(
        idx, D["lo"], D["n"], real))
    tokens = int(keep.sum())
    assert routed + absent == D["k"] * tokens
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8) & keep[:, None]
    assert routed == held.sum()
    assert hit == len(set(np.asarray(idx)[held].tolist()))
    if case == "one_held_expert":
        assert (np.asarray(idx) == 5).any(axis=1).all() and routed >= T
        assert np.abs(np.asarray(want)).min(axis=1).max() > 0
    elif case == "all_chosen_held":
        assert (routed, absent, hit) == (4 * T, 0, 4)


# -- the step's form: only the held experts that were hit ---------------------
STEP_HELD = (32, 48)        # 16 of 256 experts, 8 a token, as the cell has it


def _step_routing(case, T, seed=0):
    """``(idx (T, 8), w (T, 8), real (T,), experts hit)`` over 256 experts
    of which ``STEP_HELD`` are held: every choice absent, then the case's
    held experts planted (no expert twice in a row of ``idx``)."""
    lo, hi = STEP_HELD
    r = np.random.RandomState(seed)
    absent = np.r_[0:lo, hi:256]
    idx = np.stack([r.permutation(absent)[:8] for _ in range(T)]).astype(
        np.int32)
    real = np.ones((T,), bool)
    real[r.permutation(T)[:T // 4]] = False          # slots that hold nothing
    live = np.flatnonzero(real)
    if case == "all_16_hit":
        hit = list(range(16))
        for i, e in enumerate(r.permutation(16).tolist() * 2):
            idx[live[i % len(live)], i // len(live)] = lo + e
    elif case == "one_hit":
        hit = [9]
        idx[live[:5], 2] = lo + 9
    elif case == "none_hit":
        hit = []
    elif case == "gaps_3_7_15":
        hit = [3, 7, 15]
        for i, t in enumerate(live[:9]):
            idx[t, i % 8] = lo + hit[i % 3]
        idx[live[9], :3] = [lo + 3, lo + 7, lo + 15]
    else:                   # an expert whose only chooser is no real token
        assert case == "only_chooser_not_real"
        hit = [2, 11]
        idx[live[:3], 0] = lo + 2
        idx[live[3:6], 1] = lo + 11
        idx[np.flatnonzero(~real)[:2], 5] = lo + 6
    w = r.rand(T, 8).astype(np.float32) + 0.1
    return idx, w, real, hit


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T", [32, 128])
@pytest.mark.parametrize("case", ["all_16_hit", "one_hit", "none_hit",
                                  "gaps_3_7_15", "only_chooser_not_real"])
def test_step_kernel_reads_the_hit_experts_and_is_the_dense_form(case, T,
                                                                 dtype):
    """``_share_hit`` (the Pallas kernel over the hit experts, their ids
    scalar-prefetched; interpreted) against ``moe_share_dense``: the same
    operands in the weights' dtype, float32 sums, the float32 weights
    ``c``; only the order of the sum over experts differs (measured 1.5e-6
    in float32 and 5e-7 in bfloat16 at outputs of size 2-3; a bfloat16
    ``h`` may round the other way on a last float32 bit of its
    up-projection, so that tolerance is a bfloat16 step of one element).
    The hit list holds exactly the case's experts: one that only a slot
    without a sequence chose is not in it, and with none hit the output
    is zeros.  Two tiles of the experts' width, rows of any count."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    lo, hi = STEP_HELD
    n, d, f = hi - lo, 64, 2 * moe._EXPERT_TILE
    idx, w, real, want_hit = _step_routing(case, T)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    Eg, Eu, Ed = (
        (0.2 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
        for k, shape in zip(ks[1:], [(n, d, f), (n, d, f), (n, f, d)]))
    hit, nhit = moe._hit_list(jnp.asarray(idx), lo, n, jnp.asarray(real))
    assert np.asarray(hit)[:int(nhit)].tolist() == want_hit
    assert not np.asarray(hit)[int(nhit):].any()
    want = np.asarray(moe.moe_share_dense(x, idx, w, Eg, Eu, Ed, lo, real))
    got = np.asarray(moe._share_hit(x, idx, w, Eg, Eu, Ed, lo, real,
                                    interpret=True))
    assert got.shape == want.shape == (T, d) and got.dtype == np.float32
    assert not got[~real].any() and not want[~real].any()
    if case == "none_hit":
        assert not got.any()
    else:
        assert np.abs(want[real]).max() > 0.5
    tol = 1e-5 if dtype == "float32" else 4e-3
    assert np.abs(got - want).max() < tol * max(1.0, np.abs(want).max())


def test_hit_list_agrees_with_the_counter_on_random_routings():
    """The list of hit experts the kernel prefetches and its length
    against ``moe_share_counts``' third count (what
    ``moe_experts_hit_pct.longgen`` reads) and against the set itself,
    over random routings with random slots empty."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    lo, hi = STEP_HELD
    n = hi - lo
    seen = set()
    for seed in range(24):
        r = np.random.RandomState(seed)
        T = int(r.choice([1, 7, 32, 100]))
        # a router that leans on a few experts, so that 0..16 are hit
        among = r.permutation(256)[:int(r.choice([12, 40, 256]))]
        idx = np.stack([r.permutation(among)[:8] for _ in range(T)]).astype(
            np.int32)
        real = r.rand(T) < r.choice([0.0, 0.3, 1.0])
        hit, nhit = moe._hit_list(jnp.asarray(idx), lo, n, jnp.asarray(real))
        held = idx[real][(idx[real] >= lo) & (idx[real] < hi)] - lo
        assert np.asarray(hit)[:int(nhit)].tolist() == sorted(set(
            held.tolist()))
        assert int(nhit) == int(moe.moe_share_counts(
            jnp.asarray(idx), lo, n, jnp.asarray(real))[2])
        seen.add(int(nhit))
    assert 0 in seen and max(seen) >= 12


def test_step_form_is_the_dense_form_off_the_tpu():
    """``moe_share_step`` chooses by what the program is lowered for: on
    the CPU (and eagerly, and over the suite's eight devices) it IS
    ``moe_share_dense`` with the empty slots' rows zeroed, bit for bit,
    and no kernel lowering is counted."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel import moe
    lo, hi = STEP_HELD
    idx, w, real, _ = _step_routing("gaps_3_7_15", 32)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (32, 64), jnp.float32)
    Eg, Eu, Ed = (0.2 * jax.random.normal(k, shape, jnp.float32)
                  for k, shape in zip(ks[1:], [(16, 64, 128), (16, 64, 128),
                                               (16, 128, 64)]))
    before = moe.moe_step_kernel_lowerings()
    want = np.asarray(moe.moe_share_dense(x, idx, w, Eg, Eu, Ed, lo, real))
    step = lambda *a: moe.moe_share_step(*a, lo, jnp.asarray(real))
    args = (x, jnp.asarray(idx), jnp.asarray(w), Eg, Eu, Ed)
    np.testing.assert_array_equal(np.asarray(step(*args)), want)
    np.testing.assert_array_equal(np.asarray(jax.jit(step)(*args)), want)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    split = NamedSharding(mesh, P(None, None, "model"))
    sharded = jax.jit(step)(x, args[1], args[2], jax.device_put(Eg, split),
                            jax.device_put(Eu, split), Ed)
    assert np.abs(np.asarray(sharded) - want).max() < 1e-5
    assert moe.moe_step_kernel_lowerings() == before


# -- the model against the reference ------------------------------------------
def _close(got, want, dtype):
    """The tolerance of ``dtype``, as set out at the top."""
    err = np.abs(got - want)
    if dtype == "float32":
        return err.max() < TOL_F32
    return err.mean() < TOL_BF16_MEAN and err.max() < TOL_BF16_MAX


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference_logits(ref, family, weights, dtype):
    """The full forward (attention unabsorbed, experts grouped) on
    logits; and what the tolerance is worth: the same comparison with
    float8 inputs and weights of every matmul fails the bfloat16 one."""
    seq = _prompts([37])[0]
    w = _as(weights, dtype)
    want = np.asarray(ref.logits(TINY, w, seq))
    got = np.asarray(_lm(family, weights, dtype).forward([seq]))[0]
    assert np.ptp(want) > 0.5
    assert _close(got, want, dtype)
    if dtype == "bfloat16":
        low = np.asarray(ref.logits(TINY, w, seq, low=True))
        assert np.abs(low - want).mean() > 2 * TOL_BF16_MEAN


def _teacher_forced(lm, pool, write, step, slot, prompt, bucket, forced):
    """Prefill ``prompt`` left-padded into ``bucket`` in ``slot``, then
    one decode step a token of ``forced``: yields the logits of every
    position from the prompt's last on."""
    import jax.numpy as jnp
    pad = bucket - len(prompt)
    padded = np.asarray([[0] * pad + prompt], np.int32)
    assert pool.ensure(slot, bucket)
    logits, *state = lm.prefillRaw(padded, lengths=[len(prompt)])
    ids = jnp.asarray(pool.heldIds(slot), jnp.int32)
    pool.arrays = write(*pool.arrays, *(p[:, 0] for p in state), ids,
                        jnp.asarray(slot, jnp.int32))
    yield np.asarray(logits[0])
    S = pool.maxSlots
    pos, start, tok = (np.zeros(S, np.int32) for _ in range(3))
    pos[slot], start[slot] = bucket, pad
    for t in forced:
        assert pool.ensure(slot, int(pos[slot]) + 1)
        tok[slot] = t
        out = step(lm.params, *pool.arrays, jnp.asarray(tok[:, None]),
                   jnp.asarray(pool.pageTable), jnp.asarray(pos),
                   jnp.asarray(start))
        pool.arrays = out[1:3]
        logits = np.asarray(out[0][slot, 0])    # the step has ended: only
        pos[slot] += 1                          # now may its inputs change
        yield logits


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_and_absorbed_paged_decode_match_the_reference_logits(
        ref, family, weights, dtype):
    """Logits of every decode step, teacher-forced, through the pool's
    latent rows: a ragged left-padded prompt prefilled UNABSORBED (its
    rows rotated by position among the real tokens), 40 new tokens
    decoded ABSORBED against those rows, compared with the reference's
    unabsorbed forward; then THE SAME SLOT reused by a shorter sequence
    in another bucket whose stale rows must not reach it.  The idle
    slots' pages stay untouched, and the routing's counts of both
    prefills come back with the step after them, once."""
    import jax
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    w = _as(weights, dtype)
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    write = lm.buildPagedPrefillWriteFn()
    counted = []

    jitted = jax.jit(lm.pagedLogits)

    def step(*args):
        out = jitted(*args)
        counted.append(np.asarray(out[3]))
        return out
    for prompt, bucket in ((_prompts([11])[0], 16), (_prompts([5], 2)[0], 8)):
        forced = _prompts([40], seed=len(prompt))[0]
        seq = prompt + forced
        want = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1))
        got = np.stack(list(_teacher_forced(lm, pool, write, step, 1, prompt,
                                            bucket, forced)))
        assert _close(got, want, dtype)
        if dtype == "bfloat16":
            low = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1,
                                        low=True))
            assert np.abs(low - want).mean() > 2 * TOL_BF16_MEAN
        assert pool.release(1) == -(-(bucket + 40) // PAGE)
    counted = np.stack(counted)                  # (80 steps, 6)
    pairs = TINY["num_experts_per_tok"] * EXPERT_LAYERS
    # a step: one live slot; a prefill: its real tokens, read once
    assert (counted[:, 0] + counted[:, 1] == pairs).all()
    assert (counted[:, 3] + counted[:, 4]).tolist() == \
        [pairs * 11] + [0] * 39 + [pairs * 5] + [0] * 39
    assert not np.asarray(pool.arrays[1]).any()
    # the pages no sequence was ever given are as they were made
    assert not np.asarray(pool.arrays[0][:, pool.numPages - 10:]).any()
    assert pool.usedPages() == 0 and pool.stateSlots() == 0


def test_rotary_positions_count_the_real_tokens_under_left_padding(
        ref, family, weights):
    """A prompt prefilled with 3 pads and with 11: the same logits and
    the same latent rows at its real positions (a pad has no position and
    is no key), and they are the reference's; moved by one position, the
    rotated lanes of a row change and the latent's do not."""
    lm = _lm(family, weights, "float32")
    prompt = _prompts([5], seed=4)[0]
    outs = {}
    for bucket in (8, 16):
        padded = np.asarray([[0] * (bucket - 5) + prompt], np.int32)
        logits, rows, _ = lm.prefillRaw(padded, lengths=[5])
        outs[bucket] = (np.asarray(logits[0]),
                        np.asarray(rows[:, 0, 0, bucket - 5:]))
    assert np.abs(outs[8][0] - outs[16][0]).max() < TOL_F32
    assert np.abs(outs[8][1] - outs[16][1]).max() < TOL_F32
    want = np.asarray(ref.logits(TINY, _as(weights, "float32"), prompt,
                                 first=4))[0]
    assert np.abs(outs[8][0] - want).max() < TOL_F32
    # the same five tokens one position later (behind one real token)
    shifted = np.asarray([[0, 0, 7] + prompt], np.int32)
    rows = np.asarray(lm.prefillRaw(shifted, lengths=[6])[1][0, 0, 0, 3:])
    first = outs[8][1][0]                        # layer 0: no context yet
    assert np.abs(rows[:, :32] - first[:, :32]).max() < TOL_F32
    assert np.abs(rows[:, 32:40] - first[:, 32:40]).max() > 1e-2
    assert not rows[:, 40:].any()


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
def test_flash_prefill_attention_is_the_blocked_form(family, weights, dtype,
                                                     tol):
    """``_attend_flash`` (what a TPU runs from 1,024 positions: the causal
    flash kernel, interpreted here, over sequences turned so that their
    pads lie BEHIND the real tokens, lanes padded to whole tiles, the
    scale's difference carried by the queries) against the blocked form
    with its explicit mask, at the real positions of two sequences, one
    left-padded by 11.  Float32 to rounding (measured 6e-7 at values of
    3.5); bfloat16 differs by the softmax weights' rounding before the
    context's matmul (0.009)."""
    import jax.numpy as jnp
    lm = _lm(family, weights, dtype)
    c = lm.config
    rs = np.random.RandomState(0)
    b, T, H = 2, 32, c.nHeads
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    qn, qr = f(b, T, H, c.nopeDim), f(b, T, H, c.ropeDim)
    kn, kr, v = (f(b, T, H, c.nopeDim).astype(dtype),
                 f(b, T, c.ropeDim).astype(dtype),
                 f(b, T, H, c.vDim).astype(dtype))
    start = jnp.asarray([0, 11], jnp.int32)
    want = lm._attend_full(qn, qr, kn, kr, v, start)
    got = lm._attend_flash(qn, qr, kn, kr, v, start, interpret=True)
    real = np.arange(T)[None, :] >= np.asarray(start)[:, None]
    assert np.abs(np.asarray(want)).max() > 1.0
    assert np.abs(np.asarray(got - want))[real].max() < tol


@pytest.fixture
def batcher(family, weights):
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="pangu", maxSlots=SLOTS,
        pageSize=PAGE, numPages=1 + SLOTS * (CAP // PAGE),
        ladder=BucketLadder(batchSizes=(SLOTS,), seqLens=(8, 16)))
    cb.start()
    yield cb
    cb.shutdown()


def _served_gap(ref, weights, prompt, served):
    """How far the served tokens' reference logits lie below the
    reference's best, at their worst."""
    lg = np.asarray(ref.logits(TINY, _as(weights, "float32"),
                               (prompt + served)[:-1],
                               first=len(prompt) - 1))
    return float((lg.max(-1) - lg[np.arange(len(served)), served]).max())


def _routing(name="pangu"):
    from deeplearning4j_tpu.telemetry import serving_metrics
    sm = serving_metrics()
    return {(c, ph): getattr(sm, "moe_" + c)().value(model=name, phase=ph)
            or 0 for c in ("pairs_routed", "pairs_absent", "experts_hit")
            for ph in ("step", "prefill")}


def test_continuous_batcher_serves_the_reference_tokens_and_counts_routing(
        ref, weights, batcher):
    """Five ragged prompts in two buckets on three slots, sent at
    different moments, 40 new tokens each.  Every served token must be
    the reference's best up to float32 rounding of logits; the manager's
    books are empty afterwards; and the three routing counters, counted
    on the device and read with the tokens, are consistent: every token
    that passed an expert layer chose 4 experts in each, held here or
    absent."""
    from deeplearning4j_tpu.telemetry import serving_metrics
    # the warm-up's prefills (one real token a bucket) left their counts
    # in the pool for the first step to return: let one pass
    batcher.submit({"tokens": [1, 2], "maxNewTokens": 3})
    before = _routing()
    prompts = _prompts([5, 11, 16, 7, 3])
    outs = [None] * len(prompts)

    def go(i):
        time.sleep(0.05 * i)
        outs[i] = np.asarray(batcher.submit(
            {"tokens": prompts[i], "maxNewTokens": 40}))[0].tolist()
    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    for p, o in zip(prompts, outs):
        assert o is not None and len(o) == 40
        assert _served_gap(ref, weights, p, o) < TOL_F32
    pool = batcher.pool
    assert [a.shape for a in pool.arrays] == [
        (3, pool.numPages, PAGE, 128), (1, SLOTS, 3)]
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert pool.freePages() == pool.numPages - 1
    sm = serving_metrics()
    assert sm.cache_bytes().value(model="pangu", kind="paged") == 0
    # off the TPU the step gathers: the kernel's gauges say so
    assert sm.paged_attention_kernel().value(model="pangu") == 0
    assert sm.paged_attention_kv_passes().value(model="pangu") == 0
    assert sm.ring_attention_kernel().value(model="pangu") == 0
    # and its expert layers multiply every held expert over every slot
    assert sm.moe_step_kernel().value(model="pangu") == 0
    assert sm.moe_grouped_kernel().value(model="pangu") == 0
    got = {k: v - before[k] for k, v in _routing().items()}
    pairs = TINY["num_experts_per_tok"] * EXPERT_LAYERS
    # the last step's counts are read with its tokens; the prefills' ride
    # with the step after them (every request has one: 40 new tokens)
    assert got["pairs_routed", "prefill"] + got["pairs_absent", "prefill"] \
        == pairs * sum(len(p) for p in prompts)
    # a step counts every live slot: the 39 tokens a request decodes, and
    # the token computed beyond a quota never (its slot is free by then)
    assert got["pairs_routed", "step"] + got["pairs_absent", "step"] \
        == pairs * 39 * len(prompts)
    for ph in ("step", "prefill"):
        assert 0 < got["experts_hit", ph] <= got["pairs_routed", ph]
        assert got["pairs_routed", ph] < got["pairs_absent", ph] * 2


def test_preempt_replay_and_evacuate_return_the_same_tokens(ref, weights,
                                                            batcher):
    """A preempted sequence restarts from its prompt: prefill rebuilds
    its latent rows, the replay is teacher-forced, and the client sees
    each token once.  ``evacuate`` hands the sequences over reset the
    same way."""
    from deeplearning4j_tpu.remote.scheduler import _Seq
    prompts = _prompts([9, 6], seed=7)
    want = [np.asarray(batcher.submit(
        {"tokens": p, "maxNewTokens": 24}))[0].tolist() for p in prompts]
    streams = [batcher.submitStream({"tokens": p, "maxNewTokens": 24})
               for p in prompts]
    got = [[next(s)] for s in streams]          # both are decoding now
    done = threading.Event()

    def preempt():                              # on the loop's own thread
        slot = next(i for i, s in enumerate(batcher._slotSeq)
                    if s is not None)
        batcher._preempt(slot)
        done.set()
    orig = batcher._growPages

    def once():
        if not done.is_set():
            preempt()
        return orig()
    batcher._growPages = once
    for g, s in zip(got, streams):
        g.extend(s)
    assert done.is_set()
    assert got == want
    assert batcher.pool.usedPages() == 0 and batcher.pool.stateSlots() == 0
    streams = [batcher.submitStream({"tokens": p, "maxNewTokens": 24})
               for p in prompts]
    firsts = [next(s) for s in streams]
    seqs = batcher.evacuate()
    assert len(seqs) == 2 and all(isinstance(s, _Seq) for s in seqs)
    assert all(not s.emitted and s.forced for s in seqs)
    assert sorted(s.forced[0] for s in seqs) == sorted(firsts)
    assert batcher.pool.usedPages() == 0 and batcher.pool.stateSlots() == 0
    for s in seqs:
        assert s.forced == want[prompts.index(s.tokens[0].tolist())][
            :len(s.forced)]


def test_each_prompt_bucket_prefills_under_its_own_name(family, weights):
    """The device trace tells a bucket's prefill from another's by the
    program's name, which ``prefill_mfu_pct.longgen`` counts operations
    by; the batcher counts the jits as it counted the one."""
    lm = _lm(family, weights, "float32")
    assert lm.compileCacheSize() == 0
    for bucket in (8, 16):
        logits = lm.prefillRaw(np.zeros((1, bucket), np.int32),
                               lengths=[5])[0]
        assert logits.shape == (1, TINY["vocab_size"])
        text = lm._prefillRawFn.at(bucket).lower(
            lm.params, np.zeros((1, bucket), np.int32),
            np.zeros((1,), np.int32)).as_text()
        assert f"module @jit_prefill_{bucket} " in text
    assert lm.compileCacheSize() == 2
    lm.dropCompiled()
    assert lm.compileCacheSize() == 0


def test_published_configuration_counts_its_parameters(ref, family):
    """``jax.eval_shape`` of the published sizes as the benchmark's
    configuration cuts them: 4.92 B parameters in the dense layer, four
    expert layers of 16 held experts and an eighth of the vocabulary,
    every width as published; whole, the same shapes give 719.1 B (the
    model card says 718 B; the multi-token-prediction layer is not
    counted)."""
    import jax
    with open(os.path.join(REPO, "benchmark", "configs",
                           "openpangu_ultra_moe.json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "moe_intermediate_size", "intermediate_size", "router_width",
        "num_experts_per_tok", "routed_scaling_factor")] == [
        7680, 128, 128, 64, 128, 1536, 512, 2048, 18432, 256, 8, 2.5]
    empty = {"emb": None, "head": None, "norm_f": None, "layers": []}
    lm = family.build_lm(config, empty, config["serving"]["capacity"])
    assert (lm.config.expertsHeld, lm.config.nExperts) == ((0, 16), 256)
    shapes = jax.eval_shape(lm._init_params)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == ref.param_count(config) == 4_919_139_840
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(shapes))
    assert ref.param_count(ref.published(config)) == 719_093_767_680
    spec = lm.cacheSpec()
    assert (spec.pagedLayers, spec.pagedPools, spec.rowWidth) == (5, 1, 640)
    assert ref.cache_bytes(config)["paged"] == 5 * 2 * 576
    # the one-argument form counts no routed expert: what every step
    # reads whatever the router says
    per = ref.layer_params(config)
    assert ref.decode_step_bytes(config, 0.0, 64) \
        - ref.decode_step_bytes(config, 0.0) == 64 * 2 * per["expert"]
    assert ref.decode_step_bytes(config, 1000.0) == ref.param_bytes(config) \
        + 1000 * 5 * 2 * 576


# -- the latent attention moved to nlp/latent.py (PR 44) ---------------------
GOLDEN = os.path.join(REPO, "tests", "fixtures", "olmo_pangu_logits_pr43.npz")


def _golden(family, weights, dtype):
    """What ``fixtures/olmo_pangu_logits_pr43.npz`` holds of this model
    for ``dtype`` (keys ``pangu_<form>_<dtype>``): the full forward's
    logits of a 24-token prompt, and the logits of a left-padded prefill
    (11 tokens in the 16 bucket) and of 12 teacher-forced ABSORBED steps
    through the pool.  Recorded on commit 424521a (PR 43), where the
    latent pieces still lay in ``pangu_moe.py``, by ``np.savez`` over
    this function and ``test_olmo_hybrid._golden``, with ``pangu_canary``
    = ``ref.logits(TINY, weights, _prompts([24])[0])`` of the same
    machine."""
    import jax
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    forward = np.asarray(lm.forward(np.asarray([_prompts([24])[0]])))[0]
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    served = np.stack(list(_teacher_forced(
        lm, pool, lm.buildPagedPrefillWriteFn(), jax.jit(lm.pagedLogits), 1,
        _prompts([11])[0], 16, _prompts([12], seed=7)[0])))
    return {"forward": forward, "served": served}


@pytest.mark.parametrize("form", ["forward", "served"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_logits_are_bit_for_bit_what_they_were_before_the_latent_moved(
        ref, family, weights, dtype, form):
    """``nlp/latent.py`` computes what ``pangu_moe.py`` computed, in the
    same order, so not one bit of a logit may differ from the recording
    of PR 43's tree; where this machine's CPU rounds unlike the recording
    one (the canary differs) the logits are held to the file's tolerances
    and the case reads SKIPPED (as ``test_sambay.py``'s does)."""
    with np.load(GOLDEN) as want:
        got = _golden(family, weights, dtype)[form]
        canary = np.asarray(ref.logits(TINY, weights, _prompts([24])[0]))
        if np.array_equal(canary, want["pangu_canary"]):
            np.testing.assert_array_equal(got, want[f"pangu_{form}_{dtype}"])
            return
        assert _close(got, want[f"pangu_{form}_{dtype}"], dtype)
    pytest.skip("this machine's CPU rounds unlike the one that recorded "
                "the fixture (the canary differs): equality not checked, "
                "the logits lie within the file's tolerances")
