"""The Keye-VL served LM (``nlp/keye_vl.py``: grouped-query attention with
rotary positions whose rows a learned selector picks, a softmax router
over experts of which a chip holds a share) against the benchmark's plain
reference, at a small size on the CPU: the sparse read against the plain
formula, the prefill's kernels against their ``jax.numpy`` form, the
expert layer's shares against the uncut layer, the full forward on
logits, then prefill + decode through the scheduler's three pools.

The reference is ``benchmark/references/keyevl.py`` itself, loaded by
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

# two layers; this "chip" holds experts 4..7 of 16 and the router chooses
# 4 a token; a query reads the 6 best of its live rows
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 32, "router_width": 16,
        "experts_held": [4, 8], "num_experts": 4, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "num_hidden_layers": 2, "vocab_size": 96,
        "rms_norm_eps": 1e-6, "rope_theta": 1e7,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "topk": 6}}
TOPK = TINY["sa_config"]["topk"]
PAGE, SLOTS, CAP = 4, 3, 64
LAYERS = 2

# float32 weights on the CPU: both sides compute in float32 and differ in
# the order of their sums: measured 1.2e-7 (forward) and 2.4e-7 (paged
# decode) on logits whose spread is 0.16
TOL_F32 = 5e-6
# bfloat16 weights: the program rounds the residual stream, the rows and
# every matmul's input where the reference keeps float32, and now and then
# a rounded index key or router score chooses another row or expert than
# the float32 one: with 6 rows a query that moves a position's logits by
# up to 0.26.  Held on the MEAN error over positions and vocabulary
# (measured 0.0020-0.0041 forward), where float8 reads 0.027-0.035 and the
# selection left out 0.064-0.067; the largest (0.16-0.26, float8's
# 0.36-0.51) loosely
TOL_BF16_MEAN, TOL_BF16_MAX = 0.01, 0.8


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
    return _load("benchmark/references/keyevl.py", "bench_ref_keyevl")


@pytest.fixture(scope="module")
def family():
    return _load("benchmark/configs/keyevl.py", "bench_cfg_keyevl")


@pytest.fixture(scope="module")
def weights(ref):
    import jax
    return ref.make_weights(TINY, jax.random.PRNGKey(3))


def _as(weights, dtype):
    import jax
    return jax.tree.map(lambda a: a.astype(dtype), weights)


def _lm(family, weights, dtype, config=TINY):
    return family.build_lm(dict(config, dtype=dtype), _as(weights, dtype),
                           CAP)


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, TINY["vocab_size"], size=n).tolist()
            for n in lengths]


# -- the three pools -----------------------------------------------------------
def test_cache_spec_names_index_rows_and_the_pool_holds_a_third_array(
        family, weights):
    """An index row is ``indexWidth`` lanes stored in whole lane tiles, in
    a third array of the same pages; the byte counts follow the spec."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import CacheSpec
    from deeplearning4j_tpu.remote import KVCachePool
    spec = _lm(family, weights, "bfloat16").cacheSpec()
    assert (spec.pagedLayers, spec.kvHeads, spec.headSize, spec.indexWidth
            ) == (2, 2, 16, 8)
    assert (spec.rowWidth, spec.indexRowWidth, spec.pagedPools,
            spec.splitHeads) == (32, 128, 2, 2)
    pool = KVCachePool.forSpec(spec, PAGE, 9, SLOTS, 4)
    assert [(a.shape, a.dtype) for a in pool.arrays] == [
        ((2, 9, PAGE, 32), jnp.bfloat16), ((2, 9, PAGE, 32), jnp.bfloat16),
        ((2, 9, PAGE, 128), jnp.bfloat16), ((1, SLOTS, 9), jnp.int32)]
    assert pool.pageBytes == 2 * PAGE * 2 * 32 * 2
    assert pool.indexPageBytes == 2 * PAGE * 128 * 2
    assert CacheSpec(6, 4, 128, indexWidth=64).indexRowWidth == 128


@pytest.mark.parametrize("spec", ["gpt2", "sambay", "olmo", "pangu", "jamba"])
def test_a_model_without_a_selector_keeps_its_pool_as_it_was(spec):
    """``indexWidth`` 0, every other model's: the arrays, their order and
    the byte counts are what they were before the third pool existed."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import CacheSpec
    from deeplearning4j_tpu.remote import KVCachePool
    f32, bf = jnp.float32, jnp.bfloat16
    s, want, page = {
        "gpt2": (CacheSpec(3, 4, 16),
                 [(3, 9, 4, 64)] * 2, 3 * 4 * 2 * 64 * 4),
        "sambay": (CacheSpec(1, 2, 16, bf, ringLayers=2, ringRows=8,
                             slotState=(("ssm", (3, 4, 8), f32),)),
                   [(1, 9, 4, 32)] * 2 + [(2, 3, 8, 32)] * 2
                   + [(3, 3, 4, 8)], 1 * 4 * 2 * 32 * 2),
        "olmo": (CacheSpec(2, 2, 16, bf,
                           slotState=(("delta", (3, 2, 8, 8), f32),)),
                 [(2, 9, 4, 32)] * 2 + [(3, 3, 2, 8, 8)],
                 2 * 4 * 2 * 32 * 2),
        "pangu": (CacheSpec(3, 1, 40, bf, latentWidth=32, ropeWidth=8,
                            slotState=(("routing", (1, 3), jnp.int32),)),
                  [(3, 9, 4, 128), (1, 3, 3)], 3 * 4 * 128 * 2),
        "jamba": (CacheSpec(2, 1, 16, bf,
                            slotState=(("ssm", (4, 8, 6), f32),
                                       ("conv", (4, 3, 12), bf))),
                  [(2, 9, 4, 16)] * 2 + [(4, 3, 8, 6), (4, 3, 3, 12)],
                  2 * 4 * 2 * 16 * 2)}[spec]
    assert s.indexWidth == 0 and s.indexRowWidth == 0
    pool = KVCachePool.forSpec(s, PAGE, 9, SLOTS, 4)
    assert [a.shape for a in pool.arrays] == want
    assert pool.pageBytes == page and pool.indexPageBytes == 0
    assert not any(np.asarray(a).any() for a in pool.arrays)


# -- the sparse read against the plain formula ---------------------------------
def _plain_sparse(q, qI, wI, K, V, KI, start, topk):
    """One slot in numpy float64: ``q (H, d)``, ``qI (hI, dI)``, ``wI
    (hI,)``, rows ``K, V (n, h, d)``, ``KI (n, dI)`` of positions ``0..n -
    1``, the last the query's own; none before ``start`` is real."""
    n, h, d = K.shape
    I = (np.maximum(qI @ KI.T, 0) * wI[:, None]).sum(0)
    I[:start] = -np.inf
    order = np.argsort(-I, kind="stable")[:topk]
    order = order[order >= start]
    out = np.zeros_like(q)
    for a in range(q.shape[0]):
        g = a // (q.shape[0] // h)
        s = K[order, g] @ q[a] / np.sqrt(d)
        p = np.exp(s - s.max())
        out[a] = (p / p.sum()) @ V[order, g]
    return out


@pytest.mark.parametrize("form", ["gathered", "kernel", "in_place",
                                  "in_place_3_pages"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_sparse_read_is_the_plain_formula(dtype, tol, form, monkeypatch):
    """``paged_sparse_attention`` (its gathered form and, interpreted, its
    two one-TPU forms: the kernel over the live index pages with the sort
    and the row gather behind it, and with the selection as a mask under
    the paged-attention kernel's pass over the live pages, IN PLACE) against
    the plain formula: slots
    with fewer live rows than ``topk``, exactly ``topk`` and more; a left
    pad that swallows whole pages; a new row on a page's first and last
    place; pages in no order that still hold an earlier tenant's rows; a
    ``pos`` 0 slot, whose rows land on the scratch page and whose output
    is not read.  Tied index scores go to the earlier position.  At 3
    pages a place the masked pass takes 1 to 3 places a slot, the last of
    slots 0, 1, 3 and 4 partly dead."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    rs = np.random.RandomState(0)
    S, H, h, d, hI, dI, ps, P, L, topk = 6, 4, 2, 16, 2, 8, 4, 10, 2, 8
    li = 1
    dt = jnp.dtype(dtype)
    pos = np.array([3, 7, 29, 16, 0, 35], np.int32)      # the new row's
    start = np.array([0, 0, 9, 5, 0, 0], np.int32)       # live: 4 8 21 12 . 36
    numPages = 1 + S * P
    perm = rs.permutation(np.arange(1, numPages))
    table = perm.reshape(S, P).astype(np.int32)
    table[4] = 0                                          # holds nothing
    rnd = lambda *shape: rs.standard_normal(shape).astype(np.float32)
    poolK, poolV = (jnp.asarray(rnd(L, numPages, ps, h * d), dt)
                    for _ in range(2))
    rowsI = rnd(L, numPages, ps, dI)
    # ties: slot 5's rows 2, 3 and 20 hold one index key
    for j in (3, 20):
        rowsI[li, table[5, j // ps], j % ps] = \
            rowsI[li, table[5, 2 // ps], 2 % ps]
    poolI = jnp.asarray(np.pad(rowsI, ((0, 0),) * 3 + ((0, 128 - dI),)), dt)
    q, kN, vN = rnd(S, H, 1, d), rnd(S, h, 1, d), rnd(S, h, 1, d)
    qI, wI, kIN = rnd(S, hI, dI), rnd(S, hI), rnd(S, dI)
    if form != "gathered":
        lowered = A._attend_sparse_pages if form == "kernel" \
            else A._attend_sparse_in_place
        prim = lambda *a, li, topk: lowered(*a, li=li, topk=topk,
                                            interpret=True)
        monkeypatch.setattr(A, "_attend_sparse_p",
                            type("P", (), {"bind": staticmethod(prim)}))
    if form == "in_place_3_pages":
        monkeypatch.setattr(A, "_SELECTED_PLACE_BYTES",
                            3 * ps * h * d * dt.itemsize)
    ctx, nK, nV, nI = A.paged_sparse_attention(
        *(jnp.asarray(a) for a in (q, kN, vN, qI, wI, kIN)), poolK,
        poolV, poolI, li, jnp.asarray(table), jnp.asarray(pos),
        jnp.asarray(start), topk=topk)
    ctx = np.asarray(ctx)
    f = lambda a: np.asarray(a.astype(jnp.float32))
    nK, nV, nI = f(nK), f(nV), f(nI)
    assert not nI[..., dI:].any()
    rounded = lambda a: f(jnp.asarray(a, dt))
    for s in range(S):
        if pos[s] == 0:
            continue
        n = pos[s] + 1
        at = np.arange(n)
        rows = lambda pool: pool[li, table[s, at // ps], at % ps]
        K, V, KI = rows(nK), rows(nV), rows(nI)[:, :dI]
        # the new rows are where the page table puts position pos
        np.testing.assert_array_equal(K[-1], rounded(kN[s]).reshape(-1))
        np.testing.assert_array_equal(KI[-1], rounded(kIN[s]))
        want = _plain_sparse(
            rounded(q[s, :, 0]).astype(np.float64), qI[s].astype(np.float64),
            wI[s].astype(np.float64), K.reshape(n, h, d).astype(np.float64),
            V.reshape(n, h, d).astype(np.float64), KI.astype(np.float64),
            start[s], topk)
        np.testing.assert_allclose(ctx[s, :, 0], want, rtol=tol, atol=tol)
    # the layer that was not written is as it was
    np.testing.assert_array_equal(nK[0], f(poolK)[0])


def test_selection_mask_is_what_a_stable_sort_takes():
    """``_select_mask`` (the threshold by bisection on the bit patterns,
    the ties by position) against a stable descending sort: random
    scores, rows of equal scores, zeros of both signs, fewer valid
    columns than ``k``, none."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import _select_mask
    rs = np.random.RandomState(0)
    n, k = 40, 7
    scores = rs.standard_normal((9, n)).astype(np.float32)
    scores[1] = np.round(scores[1])                  # many ties
    scores[2] = 0.0
    scores[2, ::3] = -0.0                            # all equal: the first k
    scores[3, :20] = 1e30
    scores[4] = -np.abs(scores[4])
    valid = np.ones((9, n), bool)
    valid[5, :n - 4] = False                         # fewer than k
    valid[6] = False
    valid[7, ::2] = False
    got = np.asarray(_select_mask(jnp.asarray(scores), jnp.asarray(valid), k))
    for r in range(9):
        masked = np.where(valid[r], scores[r].astype(np.float64), -np.inf)
        order = np.argsort(-masked, kind="stable")[:k]
        want = np.zeros(n, bool)
        want[order] = True
        np.testing.assert_array_equal(got[r], want & valid[r], err_msg=str(r))


@pytest.mark.parametrize("T,starts,H,G,topk", [
    (1024, (0, 600), 4, 2, 64),
    (2048, (0, 600, 1337, 1920), 4, 2, 64),
    (1024, (0, 600), 8, 1, 64),          # the cell's eight heads a KV head
    (2048, (0, 600), 4, 2, 4)])          # a first key block with no kept key
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_prefill_kernels_are_the_blocked_form(dtype, tol, T, starts, H, G,
                                              topk):
    """``sparse_attend_full``'s two kernels (interpreted: the selection's
    tiles by bisection, flash attention under them) against its
    ``jax.numpy`` form: sequences left-padded past none, one or several
    key blocks and query blocks, 64 rows a query (or 4: then a real query
    keeps no key in the first key block it visits and some in a later
    one, so its running maximum is still at its start when the first kept
    key arrives), two or eight query heads a KV head, tied index scores.
    The kernels visit no tile that lies wholly before ``start``: what the
    pads' rows hold moves no bit of a real row."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp import keye_vl as M
    rs = np.random.RandomState(1)
    b, dh, hI, dI = len(starts), 16, 2, 8
    dt = jnp.dtype(dtype)
    rnd = lambda *shape: rs.standard_normal(shape).astype(np.float32)
    q, k, v = (rnd(b, T, n, dh) for n in (H, G, G))
    kI, qI, wI = rnd(b, T, dI), rnd(b, T, hI, dI), rnd(b, T, hI)
    kI[:, 700:720] = kI[:, 3:4]                      # ties across blocks
    kI[:, T - 100:T - 80] = kI[:, T - 120:T - 119]   # and behind any start
    start = jnp.asarray(starts, jnp.int32)
    real = np.arange(T)[None, :] >= np.asarray(starts)[:, None]
    floats = (jnp.float32,) * 2

    def run(fn, arrays, **kw):
        cast = (jnp.asarray(a, d) for a, d in zip(
            arrays, (dt, dt, dt) + floats + (dt,)))
        return np.asarray(fn(*cast, start, topk=topk, **kw
                             ).astype(jnp.float32))
    arrays = (q, k, v, qI, wI, kI)
    want = run(M._sparse_full_blocked, arrays)
    got = run(M._sparse_full_kernels, arrays, interpret=True)
    np.testing.assert_allclose(got[real], want[real], rtol=tol, atol=tol)
    assert np.isfinite(got).all()
    # other pads, large ones, and NaN in the key blocks that lie wholly
    # before ``start`` (a block that is attended, even under a mask of
    # zeros, adds 0 x NaN): the same real rows to the bit, pads finite
    Bq, Bk = M._QUERY_BLOCK, M._KEY_BLOCK
    lost = np.arange(T)[None, :] < np.asarray(starts)[:, None] // Bk * Bk
    wide = lambda m, a: m.reshape(m.shape + (1,) * (a.ndim - 2))
    other = tuple(np.where(wide(real, a), a, np.where(
        wide(lost, a), np.nan, 1e3 * rnd(*a.shape))) for a in arrays)
    again = run(M._sparse_full_kernels, other, interpret=True)
    np.testing.assert_array_equal(again[real], got[real])
    assert np.isfinite(again).all()
    # the tiles themselves: what the blocked form selects, wherever a real
    # key lies under a real query (the others are not written, nor read)
    qI, wI, kI = jnp.asarray(qI), jnp.asarray(wI), jnp.asarray(kI, dt)
    keep = np.asarray(M._select_call(start, qI, wI, kI, topk=topk,
                                     interpret=True))
    at = np.arange(T)
    valid = (at[None, None, :] <= at[None, :, None]) & real[:, None, :]
    sel = np.asarray(M._select_mask(M._index_scores(qI, wI, kI),
                                    jnp.asarray(valid), topk))
    visited = 0
    for n, s0 in enumerate(starts):
        for i in range(s0 // Bq, T // Bq):
            for c in range(s0 // Bk, (i * Bq + Bq - 1) // Bk + 1):
                visited += 1
                np.testing.assert_array_equal(
                    keep[n, i, c] != 0,
                    sel[n, i * Bq:(i + 1) * Bq, c * Bk:(c + 1) * Bk])
    assert sel.sum(-1).max() == topk
    if topk < 8:
        first = np.stack([keep[n, :, s0 // Bk] for n, s0 in enumerate(starts)]
                         ).any(-1).reshape(b, T)
        assert (real & sel.any(-1) & ~first).any()
    lm = M.KeyeVLLM(M.KeyeVLConfig(nLayers=1), params={})
    assert np.asarray(lm._prefill_tile_counts(start, T)).tolist() == [
        b * sum((i * Bq + Bq - 1) // Bk + 1 for i in range(T // Bq)),
        visited]


# -- the expert layer: a share of the experts ----------------------------------
@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(ref, form):
    """128 experts 16 to a chip, the softmax router over all 128 on every
    chip: the eight chips' parts add up to the uncut layer, in the
    reference and in the program's two forms."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel import moe
    cfg = dict(TINY, router_width=128, num_experts=128,
               experts_held=[0, 128], num_experts_per_tok=8,
               num_hidden_layers=1)
    D = ref.dims(cfg)
    key = jax.random.PRNGKey(5)
    whole = _as(ref.make_weights(cfg, key)["layers"][0]["moe"], "float32")
    x = jax.random.normal(jax.random.PRNGKey(6), (24, 64), jnp.float32)
    want = np.asarray(ref.expert_layer(x, whole, D))
    idx, w = moe.route_softmax_topk(x, whole["w_router"], 8)
    ridx, rw = ref.route(x, whole["w_router"], D)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    total = np.zeros_like(want)
    total_ref = np.zeros_like(want)
    real = jnp.ones((24,), bool)
    for chip in range(8):
        held = [16 * chip, 16 * chip + 16]
        share = dict(cfg, num_experts=16, experts_held=held)
        m = _as(ref.make_weights(share, key)["layers"][0]["moe"], "float32")
        ex = m["experts"]
        np.testing.assert_array_equal(
            np.asarray(ex["w_gate"]),
            np.asarray(whole["experts"]["w_gate"][held[0]:held[1]]))
        total_ref += np.asarray(ref.expert_layer(x, m, ref.dims(share)))
        args = (x, idx, w, ex["w_gate"], ex["w_up"], ex["w_down"], held[0])
        total += np.asarray(moe.moe_share_dense(*args) if form == "dense"
                            else moe.moe_share_grouped(*args, real))
    np.testing.assert_allclose(total_ref, want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)


# -- the model against the reference -------------------------------------------
def _close(got, want, dtype):
    """The tolerance of ``dtype``, as set out at the top."""
    err = np.abs(got - want)
    if dtype == "float32":
        return err.max() < TOL_F32
    return err.mean() < TOL_BF16_MEAN and err.max() < TOL_BF16_MAX


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference_logits(ref, family, weights, dtype):
    """The full forward on logits; and what the tolerance is worth: float8
    inputs and weights of every matmul fail it, and so does the float32
    reference with DENSE attention in the selector's place."""
    seq = _prompts([37])[0]
    w = _as(weights, dtype)
    want = np.asarray(ref.logits(TINY, w, seq))
    got = np.asarray(_lm(family, weights, dtype).forward([seq]))[0]
    assert np.ptp(want) > 0.5
    assert _close(got, want, dtype)
    dense = np.asarray(ref.logits(TINY, w, seq, dense=True))
    # the first topk positions read every row either way
    assert _close(dense[:TOPK], want[:TOPK], "float32")
    assert not _close(dense, want, dtype)
    assert np.abs(dense - want).mean() > 2 * TOL_BF16_MEAN
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
        pool.arrays = out[1:5]
        logits = np.asarray(out[0][slot, 0])    # the step has ended: only
        pos[slot] += 1                          # now may its inputs change
        yield logits


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_and_paged_decode_match_the_reference_logits(
        ref, family, weights, dtype):
    """Logits of every decode step, teacher-forced, through the pool's
    three kinds of row: a ragged left-padded prompt prefilled (its rows
    rotated by position among the real tokens, each query's selection
    among the real rows), 40 new tokens decoded against those rows, each
    step selecting 6 of up to 51 live rows, compared with the reference;
    then THE SAME SLOT reused by a shorter sequence in another bucket
    whose stale rows, index rows among them, must not reach it.  The
    counts of both prefills come back with the step after them, once."""
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
        counted.append(np.asarray(out[5]))
        return out
    for prompt, bucket in ((_prompts([11])[0], 16), (_prompts([5], 2)[0], 8)):
        forced = _prompts([40], seed=len(prompt))[0]
        seq = prompt + forced
        want = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1))
        got = np.stack(list(_teacher_forced(lm, pool, write, step, 1, prompt,
                                            bucket, forced)))
        assert _close(got, want, dtype)
        dense = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1,
                                      dense=True))
        assert not _close(dense, want, dtype)
        assert pool.release(1) == -(-(bucket + 40) // PAGE)
    counted = np.stack(counted)                  # (80 steps, 14)
    pairs = TINY["num_experts_per_tok"] * LAYERS
    assert (counted[:, 0] + counted[:, 1] == pairs).all()
    # a step scores its live rows (the new one among them) in every layer
    # and reads topk of them
    live = np.r_[11 + 1 + np.arange(40), 5 + 1 + np.arange(40)]
    assert counted[:, 3].tolist() == (LAYERS * live).tolist()
    assert counted[:, 4].tolist() == (LAYERS * np.minimum(live, TOPK)).tolist()
    # a prefill: its real tokens' pairs, read once; its queries' rows in
    # two columns (the first worth 65,536)
    assert (counted[:, 5] + counted[:, 6]).tolist() == \
        [pairs * 11] + [0] * 39 + [pairs * 5] + [0] * 39
    unit = lm.stepCounters[8][2]
    scored = counted[:, 8] * unit + counted[:, 9]
    selected = counted[:, 10] * unit + counted[:, 11]
    assert [lm.stepCounters[i][0] for i in (8, 9, 10, 11)] == \
        ["sparse_rows_scored"] * 2 + ["sparse_rows_selected"] * 2
    assert scored[[0, 40]].tolist() == [LAYERS * 66, LAYERS * 15]
    assert selected[[0, 40]].tolist() == [LAYERS * (21 + 5 * 6), LAYERS * 15]
    assert not scored[1:40].any() and not selected[41:].any()
    # and the one tile a layer that a bucket under a block is, visited
    assert [lm.stepCounters[i][0] for i in (12, 13)] == \
        ["sparse_prefill_tiles_causal", "sparse_prefill_tiles_visited"]
    assert counted[:, 12].tolist() == counted[:, 13].tolist() == \
        [LAYERS] + [0] * 39 + [LAYERS] + [0] * 39
    assert not np.asarray(pool.arrays[3]).any()
    # the pages no sequence was ever given are as they were made
    for a in pool.arrays[:3]:
        assert not np.asarray(a[:, pool.numPages - 10:]).any()
    assert pool.usedPages() == 0 and pool.stateSlots() == 0


@pytest.mark.parametrize("bucket", [8192, 16384, 32768])
@pytest.mark.parametrize("pads", [lambda bucket: 0, lambda bucket: 37,
                                  lambda bucket: bucket // 2 - 1],
                         ids=["full", "less37", "half_and_one"])
def test_prefill_counts_ride_in_two_columns_at_the_cell_s_lengths(
        family, weights, bucket, pads):
    """Sixteen prefills of 32,768 tokens score 5.2e10 pairs in six layers:
    past an int32, so the count is kept as high and low parts that each
    stay far inside one.  The kernels' tiles, the bucket's and those a
    prompt's real positions leave to visit, are a plain enumeration's and
    stay inside one column with sixteen prefills behind one step."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.keye_vl import (_COUNT_UNIT, _KEY_BLOCK,
                                                _QUERY_BLOCK, KeyeVLConfig,
                                                KeyeVLLM)
    lm = KeyeVLLM(KeyeVLConfig(nLayers=6, topk=2048), params={})
    start = pads(bucket)
    tiles = [(i, c) for i in range(bucket // _QUERY_BLOCK)
             for c in range(bucket // _KEY_BLOCK)
             if c * _KEY_BLOCK <= i * _QUERY_BLOCK + _QUERY_BLOCK - 1]
    real = [(i, c) for i, c in tiles
            if (c + 1) * _KEY_BLOCK > start and (i + 1) * _QUERY_BLOCK > start]
    got = np.asarray(lm._prefill_tile_counts(
        jnp.asarray([start] * 16, jnp.int32), bucket))
    assert got.dtype == np.int32
    assert got.tolist() == [16 * 6 * len(tiles), 16 * 6 * len(real)]
    if bucket == 32768:
        assert len(tiles) == 8320
    assert (len(real) == len(tiles)) == (start < _QUERY_BLOCK)
    # the flash kernel's grid: those tiles in order and no others run; a
    # block of pad queries keeps one step that opens and closes it (its
    # zeros); every query block is opened once and closed once; the steps
    # left over do nothing and stay on the last tile
    from deeplearning4j_tpu.nlp.keye_vl import _live_tiles
    tile, flag = (np.asarray(a) for a in _live_tiles(
        jnp.asarray([start], jnp.int32), T=bucket))
    nQ, nK = bucket // _QUERY_BLOCK, bucket // _KEY_BLOCK
    assert tile.shape == flag.shape == (len(tiles),)
    assert [divmod(t, nK) for t in tile[flag & 4 != 0]] == real
    pads = start // _QUERY_BLOCK
    live = len(real) + pads
    assert (flag[:pads] == 3).all() and (flag[live:] == 0).all()
    assert (tile[:pads] // nK).tolist() == list(range(pads))
    assert (tile[live:] == tile[live - 1]).all()
    for bit in (1, 2):
        assert (tile[flag & bit != 0] // nK).tolist() == list(range(nQ))
    n = jnp.asarray([32768] * 16, jnp.int32)
    parts = np.asarray(lm._prefill_selector_counts(n)).astype(np.int64)
    assert (parts < 2 ** 26).all()
    assert parts[0] * _COUNT_UNIT + parts[1] == 16 * 6 * 32768 * 32769 // 2
    assert parts[2] * _COUNT_UNIT + parts[3] == 16 * 6 * (
        2048 * 2049 // 2 + (32768 - 2048) * 2048)


def test_rotary_positions_count_the_real_tokens_under_left_padding(
        family, weights):
    """A prompt's last logits do not depend on the bucket it is padded
    into: a token's position is its index among the real tokens, and a
    pad is no key for the attention or for the selector."""
    lm = _lm(family, weights, "float32")
    prompt = _prompts([9])[0]
    rows = [np.asarray(lm.prefillRaw(
        np.asarray([[0] * (bucket - 9) + prompt], np.int32), lengths=[9])[0])
        for bucket in (12, 16, 32)]
    for other in rows[1:]:
        np.testing.assert_allclose(other, rows[0], rtol=0, atol=TOL_F32)


# -- behind the batcher --------------------------------------------------------
@pytest.fixture
def batcher(family, weights):
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="keye", maxSlots=SLOTS,
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


def _counted(name="keye"):
    from deeplearning4j_tpu.telemetry import serving_metrics
    sm = serving_metrics()
    return {(c, ph): getattr(sm, c)().value(model=name, phase=ph) or 0
            for c in ("moe_pairs_routed", "moe_pairs_absent",
                      "moe_experts_hit", "sparse_rows_scored",
                      "sparse_rows_selected", "sparse_prefill_tiles_causal",
                      "sparse_prefill_tiles_visited")
            for ph in ("step", "prefill")}


def test_continuous_batcher_serves_the_reference_tokens_and_counts(
        ref, weights, batcher):
    """Five ragged prompts in two buckets on three slots, sent at
    different moments, 40 new tokens each.  Every served token must be
    the reference's best up to float32 rounding of logits; the manager's
    books are empty afterwards; the routing's and the selector's
    counters, counted on the device and read with the tokens, add up; and
    the third pool's gauge followed the pages."""
    from deeplearning4j_tpu.telemetry import serving_metrics
    # the warm-up's prefills (one real token a bucket) left their counts
    # in the pool for the first step to return: let one pass
    batcher.submit({"tokens": [1, 2], "maxNewTokens": 3})
    before = _counted()
    sm = serving_metrics()
    prompts = _prompts([5, 11, 16, 7, 3])
    outs = [None] * len(prompts)
    index_bytes = []

    def go(i):
        time.sleep(0.05 * i)
        outs[i] = np.asarray(batcher.submit(
            {"tokens": prompts[i], "maxNewTokens": 40}))[0].tolist()

    def watch():
        # read while requests are in flight: a quiet machine serves one
        # in under the 50 ms to the next, and none then ends beside another
        while any(t.is_alive() for t in threads):
            index_bytes.append(sm.index_rows_bytes().value(model="keye"))
            time.sleep(0.001)
    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    watcher = threading.Thread(target=watch)
    watcher.start()
    for t in threads + [watcher]:
        t.join(120)
    for p, o in zip(prompts, outs):
        assert o is not None and len(o) == 40
        assert _served_gap(ref, weights, p, o) < TOL_F32
    pool = batcher.pool
    assert [a.shape for a in pool.arrays] == [
        (2, pool.numPages, PAGE, 32)] * 2 + [
        (2, pool.numPages, PAGE, 128), (1, SLOTS, 9)]
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert pool.freePages() == pool.numPages - 1
    assert sm.cache_bytes().value(model="keye", kind="paged") == 0
    assert sm.index_rows_bytes().value(model="keye") == 0
    assert max(index_bytes) > 0
    assert max(index_bytes) % pool.indexPageBytes == 0
    # off the TPU the step gathers: the kernels' gauges say so
    assert sm.paged_attention_kernel().value(model="keye") == 0
    assert sm.moe_step_kernel().value(model="keye") == 0
    assert sm.moe_grouped_kernel().value(model="keye") == 0
    assert sm.sparse_read_in_place().value(model="keye") == 0
    got = {k: v - before[k] for k, v in _counted().items()}
    pairs = TINY["num_experts_per_tok"] * LAYERS
    assert got["moe_pairs_routed", "prefill"] \
        + got["moe_pairs_absent", "prefill"] \
        == pairs * sum(len(p) for p in prompts)
    assert got["moe_pairs_routed", "step"] + got["moe_pairs_absent", "step"] \
        == pairs * 39 * len(prompts)
    for ph in ("step", "prefill"):
        assert 0 < got["moe_experts_hit", ph] <= got["moe_pairs_routed", ph]
    # a request's steps see n + 1 .. n + 39 rows, its prefill's queries
    # 1 .. n, in each layer; each reads at most topk of them
    n = np.asarray([len(p) for p in prompts])
    assert got["sparse_rows_scored", "step"] == LAYERS * sum(
        39 * n + 39 * 40 // 2)
    assert got["sparse_rows_selected", "step"] == LAYERS * sum(
        np.minimum(m + np.arange(1, 40), TOPK).sum() for m in n)
    assert got["sparse_rows_scored", "prefill"] == LAYERS * sum(
        n * (n + 1) // 2)
    k = np.minimum(n, TOPK)
    assert got["sparse_rows_selected", "prefill"] == LAYERS * sum(
        k * (k + 1) // 2 + (n - k) * k)
    # a bucket of 8 or 16 positions lies inside one tile of the prefill's
    # kernels, which any real token has to visit; a step has none
    for tiles in ("sparse_prefill_tiles_causal",
                  "sparse_prefill_tiles_visited"):
        assert got[tiles, "prefill"] == LAYERS * len(prompts)
        assert got[tiles, "step"] == 0


@pytest.mark.parametrize("lowered,want", [(None, 0), (LAYERS - 1, 0),
                                          (LAYERS, 1)])
def test_gauge_reads_1_where_every_sparse_layer_was_lowered_in_place(
        family, weights, monkeypatch, lowered, want):
    """The batcher reads ``sparse_in_place_lowerings`` around its warm-up's
    first step: gauge ``sparse_read_in_place`` is 1 where the step lowered
    the masked pass for every layer that selects its rows, 0 where one of
    them gathers.  On the CPU (``lowered`` None: the counters as they are)
    the gathered reference is lowered and neither counter moves; which
    form one TPU gets at which capacity is ``tests/test_tpu_compile.py``'s."""
    from deeplearning4j_tpu.nn.conf import attention as A
    from deeplearning4j_tpu.remote import (BucketLadder, ContinuousBatcher,
                                           scheduler)
    from deeplearning4j_tpu.telemetry import serving_metrics
    before = A.paged_kernel_lowerings(), A.sparse_in_place_lowerings()
    if lowered is not None:
        counts = iter([before[1], before[1] + lowered])
        monkeypatch.setattr(scheduler, "sparse_in_place_lowerings",
                            lambda: next(counts))
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="keye-gauge", maxSlots=SLOTS,
        pageSize=PAGE, numPages=1 + SLOTS * (CAP // PAGE),
        ladder=BucketLadder(batchSizes=(SLOTS,), seqLens=(8,)))
    serving_metrics().sparse_read_in_place().set(1 - want, model="keye-gauge")
    cb.start()
    try:
        assert serving_metrics().sparse_read_in_place().value(
            model="keye-gauge") == want
    finally:
        cb.shutdown()
    assert (A.paged_kernel_lowerings(),
            A.sparse_in_place_lowerings()) == before


def test_preempt_replay_and_evacuate_rebuild_the_index_rows(ref, weights,
                                                            batcher):
    """A preempted sequence restarts from its prompt: prefill rebuilds its
    three kinds of row (a replay that selected by stale index rows would
    not return the same tokens), the replay is teacher-forced, and the
    client sees each token once.  ``evacuate`` hands the sequences over
    reset the same way."""
    from deeplearning4j_tpu.remote.scheduler import _Seq
    prompts = _prompts([9, 6], seed=7)
    want = [np.asarray(batcher.submit(
        {"tokens": p, "maxNewTokens": 24}))[0].tolist() for p in prompts]
    for p, o in zip(prompts, want):
        assert _served_gap(ref, weights, p, o) < TOL_F32
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


def test_every_serving_series_covers_the_model_under_the_batcher_s_name(
        ref, weights, batcher):
    """The serving tier's telemetry knows nothing of the model: every
    ``dl4j_tpu_serving_*`` series the batcher keeps for the other served
    models is there for this one under the batcher's name, the phase
    spans and the drain clock among them, beside the selector's own."""
    from deeplearning4j_tpu.telemetry import get_registry, serving_metrics
    batcher.submit({"tokens": _prompts([9])[0], "maxNewTokens": 12})
    sm = serving_metrics()
    text = get_registry().exposition()
    mine = [ln for ln in text.splitlines()
            if ln.startswith("dl4j_tpu_serving_") and 'model="keye"' in ln]
    names = {ln.split("{", 1)[0] for ln in mine}
    for want in ("dl4j_tpu_serving_decode_steps_total",
                 "dl4j_tpu_serving_prefill_positions_total",
                 "dl4j_tpu_serving_kv_pages_in_use",
                 "dl4j_tpu_serving_cache_bytes",
                 "dl4j_tpu_serving_index_rows_bytes",
                 "dl4j_tpu_serving_slot_occupancy",
                 "dl4j_tpu_serving_loop_phase_seconds_count",
                 "dl4j_tpu_serving_device_idle_seconds_count",
                 "dl4j_tpu_serving_prefill_seconds_count",
                 "dl4j_tpu_serving_moe_pairs_routed_total",
                 "dl4j_tpu_serving_moe_experts_hit_total",
                 "dl4j_tpu_serving_sparse_rows_scored_total",
                 "dl4j_tpu_serving_sparse_rows_selected_total",
                 "dl4j_tpu_serving_sparse_prefill_tiles_causal_total",
                 "dl4j_tpu_serving_sparse_prefill_tiles_visited_total",
                 "dl4j_tpu_serving_sparse_read_in_place"):
        assert want in names, want
    assert sm.decode_steps().value(model="keye") >= 11
    phases = {ln.split('phase="')[1].split('"')[0] for ln in mine
              if ln.startswith("dl4j_tpu_serving_loop_phase_seconds_count")}
    assert {"admit", "dispatch", "fetch", "emit"} <= phases
    causes = {ln.split('cause="')[1].split('"')[0] for ln in mine
              if ln.startswith("dl4j_tpu_serving_device_idle_seconds_count")}
    assert causes and causes <= {"wait", "admit", "loop"}


def test_each_prompt_bucket_prefills_under_its_own_name(family, weights):
    """The device trace tells a bucket's prefill from another's by the
    program's name, which ``prefill_mfu_pct.longdoc`` counts operations
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
    configuration cuts them: 1.204 B parameters in six layers of 16 held
    experts with the whole vocabulary, every width as published; whole,
    the same shapes give 30.64 B."""
    import jax
    with open(os.path.join(REPO, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    sa = config["sa_config"]
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_intermediate_size", "router_width",
        "num_experts_per_tok", "vocab_size")] + [
        sa["indexer_num_heads"], sa["indexer_head_dim"],
        sa["indexer_num_kv_heads"], sa["topk"]] == [
        2048, 32, 4, 128, 768, 128, 8, 151936, 16, 64, 1, 2048]
    empty = {"emb": None, "head": None, "norm_f": None, "layers": []}
    lm = family.build_lm(config, empty, config["serving"]["capacity"])
    assert (lm.config.expertsHeld, lm.config.nExperts) == ((0, 16), 128)
    shapes = jax.eval_shape(lm._init_params)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == ref.param_count(config) == 1_203_728_640
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(shapes))
    assert ref.param_count(ref.published(config)) == 30_640_656_384
    per = ref.layer_params(config)
    assert (per["attention"] - 256, per["indexer"] - 128, per["router"],
            per["expert"]) == (18_874_368, 2_260_992, 262_144, 4_718_592)
    spec = lm.cacheSpec()
    assert (spec.pagedLayers, spec.pagedPools, spec.rowWidth,
            spec.indexRowWidth) == (6, 2, 512, 128)
    assert ref.cache_bytes(config) == {"kv": 6 * 2 * 1024, "index": 6 * 128}
    # the one-argument form counts no routed expert and no K or V row:
    # what every step reads whatever the router and the selector say
    assert ref.decode_step_bytes(config, 1000.0) == ref.param_bytes(config) \
        + 1000 * 6 * 128
    assert ref.decode_step_bytes(config, 1000.0, 64, 6000, 600) \
        == ref.param_bytes(config) + 64 * 2 * per["expert"] \
        + 6000 * 128 + 600 * 2048
    assert ref.sparse_attention_bytes(config, 1000, 100) \
        == 1000 * 128 + 100 * 2048
