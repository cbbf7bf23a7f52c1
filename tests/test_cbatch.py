"""Iteration-level continuous batching (ISSUE 15): paged KV pool,
admit/retire scheduler invariants, token streaming, KV-headroom
admission, replica fan-out (TP + DP) and the queue-depth autoscale
remediation."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nlp.transformer import TransformerLM
from deeplearning4j_tpu.remote import (AdmissionControl, ContinuousBatcher,
                                       InferenceServer, ModelRegistry,
                                       ReplicaSet, ServiceOverloaded)
from deeplearning4j_tpu.telemetry import get_registry, serving_metrics

pytestmark = pytest.mark.cbatch


def _lm(layers=1, maxLen=64, seed=5, vocab=40):
    return TransformerLM(vocabSize=vocab, nLayers=layers, nHeads=2,
                         headSize=8, maxLen=maxLen, seed=seed)


def _post(port, path, obj, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# ------------------------------------------------- paged attention ----

def _token_major(hist, ps):
    """(h, cap, d) dense history -> (cap/ps, ps, h*d) token-major pages:
    one row per position, all heads side by side."""
    h, cap, d = hist.shape
    return hist.transpose(1, 0, 2).reshape(cap // ps, ps, h * d)


@pytest.mark.parametrize("tq,layers,pos,start", [
    (1, 1, 7, 2),       # the plain decode step
    (3, 2, 6, 1),       # several rows a slot: writes 6, 7 | 8 cross a page
])
def test_paged_attention_matches_masked_softmax(tq, layers, pos, start):
    """The pooled page-table lookup is plain softmax attention over the
    slot's history with the new rows written at ``[pos, pos + tq)``,
    query ``i`` seeing key ``j`` iff ``start <= j <= pos + i`` (written
    out below, no cache); each new token lands in its own page and row
    of layer 0 of the stacked token-major pool, and nothing else — no
    other row, no other layer — is touched."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import paged_attention
    rng = np.random.RandomState(tq)
    S, h, d, ps, P = 2, 2, 4, 4, 3          # capacity = 12
    qh = jnp.asarray(rng.randn(S, h, tq, d), jnp.float32)
    kh = jnp.asarray(rng.randn(S, h, tq, d), jnp.float32)
    vh = jnp.asarray(rng.randn(S, h, tq, d), jnp.float32)
    hist_k = rng.randn(S, h, 12, d).astype(np.float32)
    hist_v = rng.randn(S, h, 12, d).astype(np.float32)
    # reference: the dense history with the new rows in place
    k, v = hist_k.astype(np.float64), hist_v.astype(np.float64)
    k[:, :, pos:pos + tq], v[:, :, pos:pos + tq] = kh, vh
    scores = np.einsum("shqd,shkd->shqk", np.asarray(qh, np.float64),
                       k) / np.sqrt(d)
    j, i = np.arange(12)[None, :], np.arange(tq)[:, None]
    scores = np.where((start <= j) & (j <= pos + i), scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    ref = np.einsum("shqk,shkd->shqd", w / w.sum(-1, keepdims=True), v)
    # paged: the same history cut into token-major pages of layer 0
    # (page 0 = scratch; slot 0 holds pages 6, 5, 4 and slot 1 pages
    # 3, 2, 1: not in physical order); every other row holds noise
    pools = {"k": rng.randn(layers, 8, ps, h * d).astype(np.float32),
             "v": rng.randn(layers, 8, ps, h * d).astype(np.float32)}
    table = np.asarray([[6 - s * P - i for i in range(P)]
                        for s in range(S)], np.int32)
    for s in range(S):
        pools["k"][0, table[s]] = _token_major(hist_k[s], ps)
        pools["v"][0, table[s]] = _token_major(hist_v[s], ps)
    got, pk, pv = paged_attention(
        qh, kh, vh, jnp.asarray(pools["k"]), jnp.asarray(pools["v"]), 0,
        jnp.asarray(table), jnp.full((S,), pos, jnp.int32),
        jnp.full((S,), start, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # the new K/V landed at [0, table[s, p // ps], p % ps], heads side by
    # side, and nothing else moved
    for new, out, before in ((kh, pk, pools["k"]), (vh, pv, pools["v"])):
        want = before.copy()
        for s in range(S):
            for i in range(tq):
                p = pos + i
                want[0, table[s, p // ps], p % ps] = \
                    np.asarray(new)[s, :, i, :].ravel()
        np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("tq", [1, 3])
def test_grouped_queries_read_their_kv_head_as_if_it_were_repeated(tq):
    """``nRep`` query heads on each stored head (read from the shapes of
    ``qh`` and of the pool's row): query head ``a`` reads KV head ``a //
    nRep``, which is what the same call gives over a pool in which every
    KV head is stored ``nRep`` times; the write lands the ``h`` new heads."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import paged_attention
    rng = np.random.RandomState(tq)
    S, h, nRep, d, ps, P = 2, 2, 3, 4, 4, 3
    new = lambda heads: jnp.asarray(rng.randn(S, heads, tq, d), jnp.float32)
    q, k, v = new(h * nRep), new(h), new(h)
    pk, pv = (jnp.asarray(rng.randn(1, 1 + S * P, ps, h * d), jnp.float32)
              for _ in range(2))

    def repeated(pool):
        return jnp.repeat(pool.reshape(pool.shape[:3] + (h, d)), nRep,
                          axis=3).reshape(pool.shape[:3] + (-1,))
    table = jnp.asarray([[1, 2, 3], [6, 5, 4]], jnp.int32)
    pos, start = jnp.asarray([5, 2], jnp.int32), jnp.asarray([1, 0], jnp.int32)
    got, gk, gv = paged_attention(q, k, v, pk, pv, 0, table, pos, start)
    want, wk, _ = paged_attention(
        q, jnp.repeat(k, nRep, axis=1), jnp.repeat(v, nRep, axis=1),
        repeated(pk), repeated(pv), 0, table, pos, start)
    assert got.shape == (S, h * nRep, tq, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(repeated(gk)), np.asarray(wk))


def _paged_case(rng, h, d, ps, P, tq, pos, start, table=None, layers=2,
                pool="float32", rep=1, halves=False):
    """Random pools (of dtype ``pool``) and float32 queries for
    ``len(pos)`` slots of ``P`` pages of ``ps`` rows: ``(qh, poolK, poolV,
    table, pos, start)`` as ``paged_attention``'s two formulations take
    them.  ``table`` defaults to each slot's pages in a scrambled
    physical order (page 0 is the scratch page and belongs to no slot).
    ``rep`` query heads read each of the ``h`` KV heads; with ``halves``
    query head ``a`` keeps half ``a % 2`` of its ``d`` lanes and zeros in
    the other (SambaY's differential pairs in a 128-lane group)."""
    import jax.numpy as jnp
    S = len(pos)
    numPages = 1 + S * P
    if table is None:
        table = 1 + rng.permutation(S * P).reshape(S, P)
    q = rng.randn(S, h * rep, tq, d)
    if halves:
        q *= (np.arange(d) // (d // 2) == np.arange(h * rep)[:, None] % 2
              )[None, :, None, :]
    return (jnp.asarray(q, jnp.float32),
            jnp.asarray(rng.randn(layers, numPages, ps, h * d), pool),
            jnp.asarray(rng.randn(layers, numPages, ps, h * d), pool),
            jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(start, jnp.int32))


# the kernel works in chunks of 128 rows: with pages of 16 rows a slot of
# 20 pages takes up to three chunks, so the running maximum, sum and
# context are carried and rescaled across them
@pytest.mark.parametrize("case", [
    # the cases of test_paged_attention_matches_masked_softmax
    dict(id="decode", h=2, d=4, ps=4, P=3, tq=1, pos=[7, 7], start=[2, 2]),
    dict(id="verify_crossing_a_page", h=2, d=4, ps=4, P=3, tq=3,
         pos=[6, 6], start=[1, 1]),
    # gpt2_xl's row: 25 heads of 64 = 1,600 lanes, no multiple of 128
    dict(id="row_of_1600_lanes", h=25, d=64, ps=16, P=10, tq=1,
         pos=[150, 37], start=[3, 0]),
    # the new row is the first of a fresh page / four rows straddle one
    dict(id="tq1_opens_a_page", h=3, d=8, ps=16, P=20, tq=1,
         pos=[160, 16, 304], start=[0, 0, 40]),
    dict(id="tq4_crossing_a_page", h=3, d=8, ps=16, P=20, tq=4,
         pos=[158, 14, 301], start=[0, 5, 130]),
    dict(id="pages_in_physical_order", h=3, d=8, ps=16, P=20, tq=1,
         pos=[200, 90], start=[0, 0], ordered=True),
    # a left pad longer than a chunk: its pages are never read
    dict(id="start_past_a_chunk", h=3, d=8, ps=16, P=20, tq=2,
         pos=[300, 250], start=[135, 249]),
    # slot 1 is inactive: every entry of its table is the scratch page
    dict(id="inactive_slot_on_the_scratch_page", h=3, d=8, ps=16, P=20,
         tq=1, pos=[77, 0, 210], start=[0, 0, 9], parked=[1]),
    dict(id="one_live_page", h=3, d=8, ps=16, P=20, tq=1,
         pos=[15, 3], start=[0, 2]),
    dict(id="the_whole_capacity", h=3, d=8, ps=16, P=20, tq=1,
         pos=[319, 318], start=[0, 100]),
    dict(id="verify_to_the_last_row", h=3, d=8, ps=16, P=20, tq=4,
         pos=[316, 40], start=[0, 0]),
    # bfloat16 pools: K and V enter the MXU as they are stored, in one
    # pass a lane tile, and q and the weights still with every bit.
    # Olmo-Hybrid's row (30 heads of 128: a head a lane tile), a slot
    # past two chunks, a left pad past a chunk
    dict(id="bf16_row_of_3840_lanes", h=30, d=128, ps=16, P=20, tq=1,
         pos=[300, 45], start=[140, 3], pool="bfloat16"),
    # the row a grouped-KV caller will bring: 20 heads of 64, two a tile
    dict(id="bf16_row_of_1280_lanes", h=20, d=64, ps=16, P=12, tq=1,
         pos=[170, 20], start=[0, 7], pool="bfloat16"),
    dict(id="bf16_verify_crossing_a_page", h=6, d=64, ps=16, P=12, tq=3,
         pos=[157, 13], start=[18, 0], pool="bfloat16"),
    # 25 heads of 64: the thirteenth lane tile is half full
    dict(id="bf16_last_tile_half_full", h=25, d=64, ps=16, P=10, tq=1,
         pos=[150, 37], start=[3, 0], pool="bfloat16"),
    # grouped queries: ``rep`` query heads read each KV head, more rows in
    # a tile's block of queries.  SambaY's paged layer as its step brings
    # it: 10 groups of 128 bfloat16 lanes, each differential query in its
    # own half of them, scores scaled for the 64 lanes that count; a left
    # pad past one chunk, and a slot that holds nothing (``pos`` 0 on the
    # scratch page)
    dict(id="grouped_2_on_10x128_in_halves", h=10, d=128, rep=2, ps=16,
         P=20, tq=1, pos=[300, 45], start=[140, 3], pool="bfloat16",
         halves=True, scale=0.125),
    dict(id="grouped_4_on_10x128_in_halves", h=10, d=128, rep=4, ps=16,
         P=20, tq=1, pos=[300, 45, 0], start=[140, 3, 0], pool="bfloat16",
         halves=True, scale=0.125, parked=[2]),
    # two heads a lane tile with three query heads each, a float32 pool
    # and four queries across a page: the heads' blocks of rows are laid
    # under one another and summed back
    dict(id="grouped_3_two_heads_a_tile_verify", h=5, d=64, rep=3, ps=16,
         P=12, tq=4, pos=[157, 13], start=[18, 0]),
    dict(id="grouped_2_bf16_two_heads_a_tile", h=20, d=64, rep=2, ps=16,
         P=12, tq=1, pos=[170, 20], start=[0, 7], pool="bfloat16"),
    # Jamba2-3B's row, the narrowest and the largest group: ONE KV head of
    # 128 bfloat16 lanes (a row is one lane tile) read by 20 query heads;
    # in pages of 16 rows (eight a chunk) and of 128 (a page a chunk)
    dict(id="grouped_20_on_one_head_of_128", h=1, d=128, rep=20, ps=16,
         P=20, tq=1, pos=[300, 45, 0], start=[140, 3, 0], pool="bfloat16",
         parked=[2]),
    dict(id="grouped_20_on_one_head_pages_of_128", h=1, d=128, rep=20,
         ps=128, P=4, tq=1, pos=[300, 45, 511], start=[140, 3, 0],
         pool="bfloat16"),
], ids=lambda c: c["id"])
def test_paged_kernel_matches_the_gathered_reference(case):
    """The TPU kernel (Pallas interpret mode, here on the CPU) against
    the reference formulation it stands in for: the same context for
    every slot, query and head, from the pages where they lie.  A
    bfloat16 pool is held to the reference run in float32 on the same
    bfloat16 rows, at the float32 cases' tolerance: only float32
    accumulation of whole products gives that (the kernel reads within
    4e-7 of the reference here; with ``q`` or the weights cut to one
    bfloat16 piece on their way into the MXU it misses by 4e-3: the
    test below)."""
    from deeplearning4j_tpu.nn.conf import attention as A
    rng = np.random.RandomState(len(case["id"]))
    S, P = len(case["pos"]), case["P"]
    table = None
    if case.get("ordered"):
        table = 1 + np.arange(S * P).reshape(S, P)
    args = _paged_case(rng, case["h"], case["d"], case["ps"], P, case["tq"],
                       case["pos"], case["start"], table,
                       pool=case.get("pool", "float32"),
                       rep=case.get("rep", 1), halves=case.get("halves"))
    for s in case.get("parked", ()):
        args = args[:3] + (args[3].at[s].set(0),) + args[4:]
    want = A._attend_gathered(*args, li=1, scale=case.get("scale"))
    got = A._attend_pages(*args, li=1, scale=case.get("scale"),
                          interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("rounded", ["q", "weights"])
def test_paged_kernel_tolerance_refuses_a_bfloat16_pass_over(rounded,
                                                             monkeypatch):
    """What the bfloat16 cases' tolerance is worth: the same kernel with
    ``q`` (or the softmax weights) going into the MXU in ONE bfloat16
    piece, its lower sixteen bits lost, fails it."""
    from deeplearning4j_tpu.nn.conf import attention as A
    args = _paged_case(np.random.RandomState(5), 20, 64, 16, 12, 1,
                       [170, 20], [0, 7], pool="bfloat16")
    want = np.asarray(A._attend_gathered(*args, li=1))
    whole = A._bf16_parts
    calls = []

    def lossy(x):
        parts = whole(x)
        calls.append(len(calls))
        # q is split a tile's block at a time (heads, lanes), the
        # weights for all tiles at once (tiles, heads, positions)
        if (rounded == "q") == (x.ndim == 2) and len(parts) > 1:
            return parts[:1] + [p * 0 for p in parts[1:]]
        return parts
    monkeypatch.setattr(A, "_bf16_parts", lossy)
    A._pages_call.clear_cache()
    try:
        got = np.asarray(A._attend_pages(*args, li=1, interpret=True))
    finally:
        monkeypatch.undo()
        A._pages_call.clear_cache()
    assert calls
    err = np.max(np.abs(got - want) - 2e-5 * np.abs(want))
    assert err > 100 * 2e-6, err


@pytest.mark.parametrize("rep", [1, 2], ids=["", "grouped"])
@pytest.mark.parametrize("how", ["slots_swapped", "pages_moved",
                                 "neighbour_changed"])
def test_paged_kernel_depends_on_a_slots_logical_content_alone(how, rep):
    """Bit for bit: two slots swapped give swapped results; the same rows
    held by other physical pages give the same result; and what another
    slot holds (its length, its rows) changes nothing — what preemption's
    replay rests on.  With two
    query heads a KV head as with one."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    rng = np.random.RandomState(11)
    h, d, ps, P = 3, 8, 16, 20
    qh, pk, pv, table, pos, start = _paged_case(
        rng, h, d, ps, P, 2, [170, 45], [20, 0], rep=rep)
    base = np.asarray(A._attend_pages(qh, pk, pv, table, pos, start, li=0,
                                      interpret=True))
    if how == "slots_swapped":
        swap = jnp.asarray([1, 0])
        got = np.asarray(A._attend_pages(
            qh[swap], pk, pv, table[swap], pos[swap], start[swap], li=0,
            interpret=True))
        np.testing.assert_array_equal(got, base[::-1])
    elif how == "pages_moved":
        perm = np.concatenate([[0], 1 + rng.permutation(2 * P)])
        inv = np.argsort(perm)          # page p's rows move to inv[p]
        got = np.asarray(A._attend_pages(
            qh, pk[:, perm], pv[:, perm], jnp.asarray(inv)[table], pos,
            start, li=0, interpret=True))
        np.testing.assert_array_equal(got, base)
    else:
        pages1 = np.asarray(table[1])
        got = np.asarray(A._attend_pages(
            qh.at[1].set(0.5), pk.at[0, pages1].set(1.0), pv, table,
            pos.at[1].set(300), start.at[1].set(64), li=0, interpret=True))
        np.testing.assert_array_equal(got[0], base[0])


@pytest.mark.parametrize("read", ["gathered", "kernel"])
def test_differential_pairs_through_the_grouped_read(read, monkeypatch):
    """SambaY's paged layer through ``paged_attention_read``: its 8 query
    heads as grouped queries of 2 groups of ``2*dh`` lanes, each in its own
    half, the pair's ``a1 - lambda a2`` taken of the two CONTEXTS and the
    sub-norm after it, give what ``SambaYLM._diff_attend`` gives on the
    gathered rows with the difference taken of the softmax weights — in
    both lowerings of the read (the kernel in interpret mode)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp import sambay
    from deeplearning4j_tpu.nn.conf import attention as A
    lm = sambay.SambaYLM(sambay.SambaYConfig(dtype="float32", seed=3))
    c = lm.config
    li = c.layerKinds().index("full")
    lp = dict(lm.params["layers"][li])
    rng = np.random.RandomState(7)
    lp["sublnG"] = jnp.asarray(1 + 0.3 * rng.randn(2 * c.headSize),
                               jnp.float32)
    S, ps, P = 3, 16, 12
    pos, start = [150, 33, 0], [20, 0, 0]
    _, k, v, table, pos, start = _paged_case(
        rng, c.nKvHeads, c.headSize, ps, P, 1, pos, start, layers=1)
    table = table.at[2].set(0)              # a slot that holds nothing
    q = jnp.asarray(rng.randn(S, 1, c.nHeads * c.headSize), jnp.float32)
    cap = P * ps
    kpos = jnp.arange(cap)[None, :]
    valid = ((kpos <= pos[:, None]) & (kpos >= start[:, None]))[:, None]
    want = lm._diff_attend(lp, li, q, k[0, table].reshape(S, cap, -1),
                           v[0, table].reshape(S, cap, -1), valid)
    if read == "kernel":
        monkeypatch.setattr(
            sambay, "paged_attention_read",
            lambda qh, pk, pv, layer, *a, scale: A._attend_pages(
                qh, pk, pv, *a, li=layer, scale=scale, interpret=True))
    got = lm._diff_attend_paged(lp, li, q, k, v, 0, table, pos, start)
    assert got.shape == want.shape == (S, 1, c.nHeads * c.headSize)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_paged_attention_lowers_the_reference_off_the_tpu():
    """``paged_attention`` chooses where it is lowered, from what it is
    lowered for: on the CPU that is the gathered reference (no kernel is
    counted), and the batcher's gauge says so."""
    import jax
    from deeplearning4j_tpu.nn.conf import attention as A
    args = _paged_case(np.random.RandomState(0), 2, 4, 4, 3, 1, [5, 2],
                       [0, 1])
    before = A.paged_kernel_lowerings()
    text = jax.jit(lambda q, k, v, *a: A.paged_attention_read(
        q, k, v, 0, *a)).lower(*args).as_text()
    assert "custom_call" not in text
    assert A.paged_kernel_lowerings() == before
    cb = ContinuousBatcher(_lm(), name="gauge-lm", maxSlots=2, pageSize=4)
    try:
        cb.warm()
    finally:
        cb.shutdown()
    assert get_registry().get(
        "dl4j_tpu_serving_paged_attention_kernel").value(
            model="gauge-lm") == 0
    # and a model without an expert layer has no expert kernel anywhere,
    # one without window layers no ring to read: both series are exposed
    assert get_registry().get(
        "dl4j_tpu_serving_moe_step_kernel").value(model="gauge-lm") == 0
    assert get_registry().get(
        "dl4j_tpu_serving_ring_attention_kernel").value(
            model="gauge-lm") == 0


def test_kv_passes_gauge_reads_zero_for_a_gathered_step():
    """``dl4j_tpu_serving_paged_attention_kv_passes``: the MXU passes over
    one K (and one V) lane tile a chunk in the step as it was lowered,
    which is what the pool's dtype needs to enter the MXU whole (bfloat16
    1, float32 3).  Here on the CPU the step gathers: 0, whatever a kernel
    lowering under the same name left behind."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import attention as A
    assert [A._mxu_parts(t) for t in (jnp.bfloat16, jnp.float16,
                                      jnp.float32)] == [1, 2, 3]
    x = jnp.asarray(np.random.RandomState(3).randn(4, 9) * 7, jnp.float32)
    parts = A._bf16_parts(x)
    assert all(np.array_equal(np.asarray(p), np.asarray(
        p.astype(jnp.bfloat16).astype(jnp.float32))) for p in parts)
    np.testing.assert_array_equal(
        np.asarray((parts[2] + parts[1]) + parts[0]), np.asarray(x))
    gauge = serving_metrics().paged_attention_kv_passes()
    gauge.set(3, model="passes-lm")
    cb = ContinuousBatcher(_lm(), name="passes-lm", maxSlots=2, pageSize=4)
    try:
        cb.warm()
    finally:
        cb.shutdown()
    assert get_registry().get(
        "dl4j_tpu_serving_paged_attention_kv_passes").value(
            model="passes-lm") == 0


def test_prefill_write_then_paged_step_equals_forward_logits():
    """The prefill write puts position ``t`` of layer ``l`` (all heads side
    by side) at ``[l, pageIds[t // ps], t % ps]``; and, end to end, a
    LEFT-padded prompt prefilled and written into pool pages, then decoded
    teacher-forced through ``pagedLogits``, gives ``lm.forward``'s logits
    row for row — ``chip_smoke.py``'s own check, as it runs on the chip."""
    import chip_smoke
    import jax.numpy as jnp
    from deeplearning4j_tpu.remote import KVCachePool
    L, h, d, ps, ids = 2, 2, 4, 4, [3, 1]
    stack = np.random.RandomState(2).randn(L, h, 2 * ps, d).astype(np.float32)
    pool = KVCachePool(L, h, d, ps, numPages=5, maxSlots=2, maxPagesPerSeq=4)
    assert pool.k.shape == pool.v.shape == (L, 5, ps, h * d)
    pk, pv = _lm(layers=L).buildPagedPrefillWriteFn()(
        pool.k, pool.v, jnp.asarray(stack), jnp.asarray(-stack),
        jnp.asarray(ids, jnp.int32))
    want = np.zeros(pool.k.shape, np.float32)
    for t in range(2 * ps):
        want[:, ids[t // ps], t % ps] = stack[:, :, t].reshape(L, h * d)
    np.testing.assert_array_equal(np.asarray(pk), want)
    np.testing.assert_array_equal(np.asarray(pv), -want)

    r = chip_smoke.Report()             # the decode crosses two pages
    chip_smoke._check_paged_parity(r, _lm(layers=2, maxLen=32), pageSize=4,
                                   promptLen=5, bucket=8, decodeSteps=6)
    assert not r.failed, r.failed
    assert r.values["paged_rows"] == 7
    assert r.values["paged_greedy_mismatches"] == 0
    assert r.values["paged_logit_err"] <= 1e-4 * r.values["paged_logit_scale"]


# --------------------------------------- scheduler core invariants ----

def test_continuous_batching_matches_generate_with_flat_misses():
    """One batcher lifecycle: ragged concurrent requests match
    ``lm.generate`` token-for-token, streaming yields the same tokens
    incrementally, admit/retire churn never compiles a new executable
    after warm-up, and retirement returns every page to the free
    list."""
    lm = _lm(layers=1)
    ref_lm = _lm(layers=1)      # references compile on a SEPARATE
    # instance so the flat-miss probe sees only the batcher's own fns
    cb = ContinuousBatcher(lm, name="cb-core", pageSize=8,
                           maxSlots=3).start()
    try:
        rng = np.random.RandomState(0)
        seen = cb.compileCacheSize()
        assert seen > 0                       # the warm ladder compiled
        # ragged lengths from a SMALL set: the reference's dense prefill
        # compiles per exact length, and that cost is the test's tail
        lens = (5, 9, 14, 23)
        prompts = [rng.randint(1, 40, (1, lens[int(rng.randint(4))])
                               ).astype(np.int32) for _ in range(7)]
        quotas = [int(rng.randint(2, 10)) for _ in range(7)]
        outs = [None] * 7

        def run(i):
            outs[i] = cb.submit({"tokens": prompts[i][0].tolist(),
                                 "maxNewTokens": quotas[i]}, timeout=120)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(7)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert all(not th.is_alive() for th in threads)  # bounded wait
        for i in range(7):
            np.testing.assert_array_equal(
                outs[i], ref_lm.generate(prompts[i], quotas[i]))
        # a multi-row request fans out and reassembles in row order
        pb = rng.randint(1, 40, (2, 9)).astype(np.int32)
        np.testing.assert_array_equal(
            cb.submit({"tokens": pb.tolist(), "maxNewTokens": 5},
                      timeout=120),
            ref_lm.generate(pb, 5))
        # streaming delivers the same tokens, incrementally
        ps = rng.randint(1, 40, (1, 9)).astype(np.int32)
        toks = list(cb.submitStream({"tokens": ps[0].tolist(),
                                     "maxNewTokens": 6}))
        assert toks == ref_lm.generate(ps, 6)[0].tolist()
        # invariants: flat jit misses across all that churn, every page
        # back on the free list, zero recorded compile misses
        assert cb.compileCacheSize() == seen
        assert cb.pool.freePages() == cb.pool.numPages - 1
        assert serving_metrics().compile_misses().value(
            model="cb-core") == 0
        assert serving_metrics().sequences_retired().value(
            model="cb-core") >= 10
    finally:
        cb.shutdown()


def test_admit_mid_decode_never_changes_earlier_tokens():
    """Admitting B while A decodes must not perturb A's token stream —
    slots are independent rows of the shared fixed-shape step."""
    lm = _lm(layers=1)
    rng = np.random.RandomState(3)
    pa = rng.randint(1, 40, (1, 11)).astype(np.int32)
    pb = rng.randint(1, 40, (1, 4)).astype(np.int32)
    refA = lm.generate(pa, 24)
    refB = lm.generate(pb, 5)
    from deeplearning4j_tpu.remote import BucketLadder
    cb = ContinuousBatcher(lm, name="cb-admit", pageSize=8, maxSlots=2,
                           ladder=BucketLadder(batchSizes=(2,),
                                               seqLens=(16,))).start()
    try:
        outA = [None]
        ta = threading.Thread(target=lambda: outA.__setitem__(
            0, cb.submit({"tokens": pa[0].tolist(), "maxNewTokens": 24},
                         timeout=120)))
        ta.start()
        time.sleep(0.05)                      # A is mid-decode
        outB = cb.submit({"tokens": pb[0].tolist(), "maxNewTokens": 5},
                         timeout=120)
        ta.join(timeout=120)
        np.testing.assert_array_equal(outA[0], refA)
        np.testing.assert_array_equal(outB, refB)
    finally:
        cb.shutdown()


def test_preemption_restarts_and_recovers_bit_identical():
    """A pool too small for two full sequences: the younger slot is
    preempted (pages freed, requeued at the front), restarts, and still
    produces the exact greedy stream; the oldest slot always progresses
    (no ping-pong livelock)."""
    lm = _lm(layers=1, maxLen=48, seed=6)
    # both requests pass the door before the loop takes a step (by hand:
    # behind a running loop the second one, a thread start later, met a
    # pool the first had already grown into and was shed 429)
    cb = _by_hand(lm, "cb-preempt", numPages=9, maxSlots=2)
    try:
        rng = np.random.RandomState(1)
        pa = rng.randint(1, 40, (1, 12)).astype(np.int32)
        pb = rng.randint(1, 40, (1, 12)).astype(np.int32)
        gens = [_stream(cb, p[0].tolist(), 30)[0] for p in (pa, pb)]
        _run_out(cb)
        res = [np.asarray([list(g)], np.int32) for g in gens]
        np.testing.assert_array_equal(res[0], lm.generate(pa, 30))
        np.testing.assert_array_equal(res[1], lm.generate(pb, 30))
        assert serving_metrics().preemptions().value(
            model="cb-preempt") >= 1
        assert cb.pool.freePages() == cb.pool.numPages - 1
    finally:
        cb.shutdown()


# ------------------------------------ the loop one step ahead ----

def _count(name, model):
    return int(get_registry().get(
        f"dl4j_tpu_serving_{name}_total").value(model=model))


def _by_hand(lm, name, **kw):
    """A warmed batcher that takes requests and has no loop thread: the
    test calls ``_iterate`` itself, so what is unread on the device at
    every point of a scenario is known, with no clock in it."""
    cb = ContinuousBatcher(lm, name=name, pageSize=8, **kw)
    cb.start()
    with cb._cv:
        cb._running = False
        cb._cv.notify_all()
    cb._thread.join(10)
    assert not cb._thread.is_alive()
    cb._thread = None
    cb._running = True
    return cb


def _run_out(cb, limit=400):
    """Iterate until nothing is queued, held or unread."""
    for _ in range(limit):
        if cb._idle():
            return
        cb._iterate()
    raise AssertionError("the batcher did not run out of work")


def _greedy(lm, prompt, n, eos=None):
    """``generate``'s tokens, cut after the first ``eos``."""
    out = lm.generate(np.asarray([prompt], np.int32), n)[0].tolist()
    return out[:out.index(eos) + 1] if eos in out else out


def _stream(cb, prompt, n):
    """Submit one streamed request; returns (its generator, its _Seq)."""
    gen = cb.submitStream({"tokens": list(prompt), "maxNewTokens": n})
    return gen, cb._queue[-1]


def test_one_step_ahead_serves_generates_tokens_under_churn():
    """The real loop, more requests than slots, mixed lengths, streamed
    and returned: token for token the unbatched greedy ``generate``; the
    loop was a step ahead for most steps, no token was computed for
    nothing (every end is a quota's, known ahead), nothing compiled and
    every page came back."""
    lm, ref_lm = _lm(layers=1), _lm(layers=1)
    cb = ContinuousBatcher(lm, name="cb-ahead", pageSize=8,
                           maxSlots=3).start()
    try:
        seen = cb.compileCacheSize()
        rng = np.random.RandomState(4)
        lens, n = (4, 9, 14, 23), 9
        prompts = [rng.randint(1, 40, lens[i % 4]).tolist()
                   for i in range(n)]
        quotas = [int(rng.randint(2, 24)) for _ in range(n)]
        outs = [None] * n

        def run(i):
            req = {"tokens": prompts[i], "maxNewTokens": quotas[i]}
            outs[i] = list(cb.submitStream(req)) if i % 2 else \
                cb.submit(req, timeout=120)[0].tolist()
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert all(not th.is_alive() for th in threads)
        for i in range(n):
            assert outs[i] == _greedy(ref_lm, prompts[i], quotas[i]), i
        steps = _count("decode_steps", "cb-ahead")
        # a step is not ahead only when it follows an idle loop
        assert _count("decode_steps_overlapped", "cb-ahead") >= 0.8 * steps
        assert _count("decode_tokens_discarded", "cb-ahead") == 0
        assert cb.compileCacheSize() == seen
        assert _count("compile_cache_misses", "cb-ahead") == 0
        assert cb.pool.usedPages() == 0
    finally:
        cb.shutdown()


@pytest.mark.parametrize("cause", ["eos", "cancel", "deadline"])
def test_an_end_learnt_a_step_late_costs_one_discarded_token(cause):
    """B ends while the step after its last is already dispatched: that
    step's token for B is discarded by B's identity (the counter reads
    exactly 1), B's pages are free at once, and C, admitted into B's slot
    while that step is still unread, never sees B's token.  A, beside
    them, is untouched."""
    from deeplearning4j_tpu.remote.serving import DeadlineExceeded
    lm, ref = _lm(layers=1), _lm(layers=1)  # the reference compiles apart
    rng = np.random.RandomState(0)
    pa, pb, pc = (rng.randint(1, 40, k).tolist() for k in (9, 6, 12))
    refB = _greedy(ref, pb, 12)
    # an end-of-sequence token B reaches at its third token and A never
    eos = refB[2]
    assert eos not in refB[:2] and eos not in _greedy(ref, pa, 14)
    name = f"cb-late-{cause}"
    cb = _by_hand(lm, name, maxSlots=2,
                  eosToken=eos if cause == "eos" else None)
    try:
        seen = cb.compileCacheSize()
        ga, sa = _stream(cb, pa, 14)
        gb, sb = _stream(cb, pb, 12)
        cb._iterate()                   # both admitted, step 1 dispatched
        cb._iterate()                   # step 2 dispatched, step 1 read
        assert cb._inflight is not None and cb._slotSeq == [sa, sb]
        assert sb.emitted == refB[:2]
        if cause == "cancel":
            assert next(gb) == refB[0]
            gb.close()                  # the client hangs up
        elif cause == "deadline":
            sb.deadline = time.monotonic() - 1.0
        gc_, sc = _stream(cb, pc, 7)
        # step 3 goes out with B in it (deadline: swept before it), then
        # step 2 is read: B's end is learnt (eos: from that very token)
        cb._iterate()
        assert cb._slotSeq == [sa, None] and cb.pool.heldIds(1) == []
        assert cb._inflight is not None
        late = cb._inflight.seqs[1] is sb       # B's token nobody wants
        assert late == (cause != "deadline")
        assert _count("decode_tokens_discarded", name) == (0 if late else 1)
        cb._iterate()                   # C admitted behind the unread step
        assert cb._slotSeq == [sa, sc]
        assert _count("decode_tokens_discarded", name) == 1
        _run_out(cb)
        assert list(ga) == _greedy(ref, pa, 14)
        assert list(gc_) == _greedy(ref, pc, 7, eos if cause == "eos"
                                    else None)
        if cause == "eos":
            assert list(gb) == refB[:3]
        elif cause == "deadline":
            with pytest.raises(DeadlineExceeded):
                list(gb)
        assert sb.emitted == (refB[:3] if cause == "eos" else refB[:2])
        assert _count("decode_tokens_discarded", name) == 1
        assert cb.pool.usedPages() == 0 and cb._inflight is None
        assert cb.compileCacheSize() == seen
    finally:
        cb.shutdown()


@pytest.mark.parametrize("then", ["refill", "cancel", "fail_over", "fail",
                                  "evacuate", "shutdown"])
def test_a_quota_end_frees_its_slot_when_its_last_step_goes_out(then):
    """B's quota is known ahead: the dispatch of the step that computes
    its last token frees B's slot and pages, so C is admitted in the next
    iteration, as it was when every step was read at once, and no step is
    computed for nothing.  B's unread tokens find it by the flight's
    record, not by the slot (C's by then); a cancel drops them; a failed
    batch, an evacuation and a shutdown treat B like a sequence in a
    slot."""
    lm, ref = _lm(layers=1), _lm(layers=1)
    rng = np.random.RandomState(2)
    pa, pb, pc = (rng.randint(1, 40, k).tolist() for k in (9, 6, 12))
    refB = _greedy(ref, pb, 3)
    name = f"cb-part-{then}"
    cb = _by_hand(lm, name, maxSlots=2)
    try:
        seen = cb.compileCacheSize()
        ga, sa = _stream(cb, pa, 14)
        gb, sb = _stream(cb, pb, 3)
        cb._iterate()                   # both admitted, step 1 dispatched
        assert cb.compileCacheSize() == seen    # the first real dispatch
        cb._iterate()                   # step 2 (B's last) out, step 1 read
        assert cb._slotSeq == [sa, None] and cb.pool.heldIds(1) == []
        assert cb._parted == [sb] and cb._inflight.seqs[1] is sb
        assert sb.emitted == refB[:2]
        assert cb.compileCacheSize() == seen    # and one fed from a step
        if then in ("refill", "cancel"):
            if then == "cancel":
                assert next(gb) == refB[0]
                gb.close()
            gc_, sc = _stream(cb, pc, 7)
            cb._iterate()               # C into B's slot; step 2 read
            assert cb._slotSeq == [sa, sc] and cb._parted == []
            assert cb._inflight.seqs == [sa, sc]
            assert sb.emitted == (refB if then == "refill" else refB[:2])
            cb._iterate()               # step 3 read: A and C, as 1 and 2
            assert cb._steps == 3 and cb.occupancy() == 1.0
            _run_out(cb)
            if then == "refill":
                assert list(gb) == refB
            assert list(ga) == _greedy(ref, pa, 14)
            assert list(gc_) == _greedy(ref, pc, 7)
        elif then in ("fail_over", "fail"):
            handed = []
            if then == "fail_over":
                cb.onSequenceFailure = lambda src, seqs, err: \
                    handed.extend(seqs)
            cb._failBatch(RuntimeError("boom"))
            assert cb._parted == [] and cb._inflight is None
            if then == "fail_over":
                assert handed == [sa, sb]
                assert sb.forced == refB[:2] and sb.emitted == []
            else:
                with pytest.raises(RuntimeError, match="boom"):
                    list(gb)
        elif then == "evacuate":
            assert cb.evacuate() == [sa, sb]
            assert sb.forced == refB[:2] and cb._parted == []
        else:
            cb.shutdown()
            assert cb._parted == []
            with pytest.raises(RuntimeError, match="shut down"):
                list(gb)
        assert _count("decode_tokens_discarded", name) == 0
        assert cb.pool.usedPages() == 0
        if not then.startswith("fail"):     # that rebuilds pools and fns
            assert cb.compileCacheSize() == seen
    finally:
        cb.shutdown()


def test_a_parted_sequence_keeps_the_batcher_busy_until_it_is_read():
    """A drain (``ReplicaSet._drainStop``) shuts a replica down when it is
    no longer ``busy()``: a sequence that has given up its slot and is
    still owed its last token counts."""
    lm, ref = _lm(layers=1), _lm(layers=1)
    prompt = np.random.RandomState(3).randint(1, 40, 7).tolist()
    cb = _by_hand(lm, "cb-part-busy", maxSlots=2)
    try:
        g, seq = _stream(cb, prompt, 3)
        cb._iterate()
        cb._iterate()
        assert cb._slotSeq == [None, None] and cb._parted == [seq]
        assert cb.busy() and not cb._idle()
        cb._iterate()                   # nothing to dispatch: only the read
        assert not cb.busy() and cb._idle()
        assert list(g) == _greedy(ref, prompt, 3)
        assert cb.pool.usedPages() == 0
    finally:
        cb.shutdown()


def test_a_step_uploads_only_the_inputs_whose_values_changed():
    """``_dispatch`` hands the step the device arrays of the step before
    for every small input whose VALUES did not change (page table, start,
    the tokens of slots that go on) and uploads the rest in one transfer:
    ``pos`` every step, the page table when a slot takes a page, all of
    them at an admission; the tokens served are ``generate``'s."""
    lm, ref = _lm(layers=1), _lm(layers=1)
    first = np.random.RandomState(11).randint(1, 40, 6).tolist()
    second = np.random.RandomState(12).randint(1, 40, 5).tolist()
    cb = _by_hand(lm, "cb-upload", maxSlots=2)
    try:
        g1, _ = _stream(cb, first, 14)
        kept = []
        for i in range(12):
            cb._iterate()
            kept.append(cb._uploaded)
        for (hostA, devA), (hostB, devB) in zip(kept[1:], kept[2:]):
            for i, (a, b) in enumerate(zip(hostA, hostB)):
                assert (devA[i] is devB[i]) == np.array_equal(a, b)
                np.testing.assert_array_equal(np.asarray(devB[i]), b)
        pt, pos, start, tok = range(4)
        reused = [[a is b for a, b in zip(devA, devB)]
                  for (_, devA), (_, devB) in zip(kept[1:], kept[2:])]
        assert all(r[start] and r[tok] and not r[pos] for r in reused)
        # pages of 8 rows: of these ten steps one, and only one, found a
        # slot on a new page
        assert sum(not r[pt] for r in reused) == 1
        g2, _ = _stream(cb, second, 4)      # an admission moves them all
        before = cb._uploaded[1]
        cb._iterate()
        assert not any(a is b for a, b in zip(before, cb._uploaded[1]))
        _run_out(cb)
        assert list(g1) == _greedy(ref, first, 14)
        assert list(g2) == _greedy(ref, second, 4)
        assert cb.pool.usedPages() == 0
    finally:
        cb.shutdown()


@pytest.mark.parametrize("how", ["preempted_by_hand", "pool_squeeze"])
def test_preempt_defer_and_replay_with_a_step_unread_deliver_once(how):
    """A preemption with a step unread loses that step's token for the
    victim and nothing else: the replay is teacher-forced from what was
    emitted (forced tokens go to the step from the host, known ahead),
    ``streamSkip`` swallows the re-emission, and each client sees each
    token once and in order.  ``pool_squeeze``: a pool too small for two
    sequences preempts the younger and defers it for rounds on end."""
    lm, ref = (_lm(layers=1, maxLen=48, seed=6) for _ in range(2))
    rng = np.random.RandomState(1)
    pa, pb = (rng.randint(1, 40, 12).tolist() for _ in range(2))
    name = f"cb-replay-{how}"
    cb = _by_hand(lm, name, maxSlots=2,
                  numPages=9 if how == "pool_squeeze" else None)
    try:
        seen = cb.compileCacheSize()
        ga, sa = _stream(cb, pa, 30)
        gb, sb = _stream(cb, pb, 30)
        events = {"grows": 0, "deferred_ahead": 0, "forced_ahead": 0}
        grow = cb._growPages

        def watched():
            events["grows"] += 1
            if how == "preempted_by_hand" and events["grows"] == 7:
                # where the loop preempts: after the admissions, before
                # the dispatch, with step 6 (B in it) unread
                assert cb._inflight.seqs[1] is sb and len(sb.emitted) == 6
                cb._preempt(1)
            active, deferred = grow()
            ahead = cb._inflight
            if ahead is not None:
                events["deferred_ahead"] += any(
                    ahead.seqs[s] is None for s in deferred)
                events["forced_ahead"] += any(
                    ahead.seqs[s] is cb._slotSeq[s] and
                    len(cb._slotSeq[s].emitted) < len(cb._slotSeq[s].forced)
                    for s in active)
            return active, deferred
        cb._growPages = watched
        _run_out(cb)
        assert list(ga) == _greedy(ref, pa, 30)
        assert list(gb) == _greedy(ref, pb, 30)
        assert sb.restarts >= 1 and sb.streamSkip == 0
        assert _count("preemptions", name) == sb.restarts + sa.restarts
        # each preemption found the victim's next token on the device
        assert _count("decode_tokens_discarded", name) == \
            _count("preemptions", name)
        assert events["forced_ahead"] >= 1
        if how == "pool_squeeze":
            assert sa.restarts == 0         # the oldest always progresses
            assert events["deferred_ahead"] >= 1
        assert cb.pool.usedPages() == 0 and cb._inflight is None
        assert cb.compileCacheSize() == seen
    finally:
        cb.shutdown()


# ----------------------------------------- dropping what was compiled ----

def _served(kind):
    if kind == "transformer":
        return _lm(layers=1)
    if kind == "retrieval":
        from deeplearning4j_tpu.models.recsys import RetrievalLM
        table = np.random.RandomState(0).randn(64, 8).astype(np.float32)
        return RetrievalLM(table, table, maxLen=64)
    from deeplearning4j_tpu.nlp.sambay import SambaYConfig, SambaYLM
    return SambaYLM(SambaYConfig())


@pytest.mark.parametrize("kind", ["transformer", "retrieval", "sambay"])
def test_invalidate_drops_every_jit_and_warm_rebuilds_the_served_set(kind):
    """A change of pool or plan leaves no compiled closure behind, on
    the batcher or on the model, and the next warm compiles the served
    set again and nothing else: prefill on the model; step and write on
    the batcher."""
    from deeplearning4j_tpu.remote import BucketLadder
    lm = _served(kind)
    cb = ContinuousBatcher(lm, name=f"cb-drop-{kind}", pageSize=4,
                           maxSlots=3,
                           ladder=BucketLadder(batchSizes=(3,),
                                               seqLens=(8, 16)))
    cb.warm()
    first = cb.compileCacheSize()
    assert lm.compileCacheSize() == 2           # one prefill a bucket
    assert first > lm.compileCacheSize()
    cb._invalidateFns()
    assert not cb._stepFns and cb.compileCacheSize() == 0
    assert not {"_fwd", "_prefillRawFn"} & set(vars(lm))
    cb.warm()
    assert set(cb._stepFns) == {"step", "write"}
    assert {"_fwd", "_prefillRawFn"} & set(vars(lm)) == {"_prefillRawFn"}
    assert cb.compileCacheSize() == first


# --------------------------------- admission + enqueue-time rejection ----

def test_kv_headroom_sheds_and_enqueue_rejects():
    """Page exhaustion degrades at the door: a request whose pages can't
    fit the free list sheds 429 with a Retry-After derived from the
    retire rate; impossible requests (prompt above the top bucket,
    quota past the page budget, zero rows) are offender-only 400s at
    enqueue time — they can never wedge or poison the shared batch."""
    lm = _lm(layers=1, maxLen=48, seed=6)
    cb = ContinuousBatcher(lm, name="cb-shed", pageSize=8, numPages=9,
                           maxSlots=2,
                           admission=AdmissionControl(retryAfter=0.5)
                           ).start()
    try:
        # enqueue-time 400s — before any queueing
        with pytest.raises(ValueError, match="exceeds the top bucket"):
            cb.submit({"tokens": list(range(1, 30)) * 2,
                       "maxNewTokens": 4})
        with pytest.raises(ValueError, match="positional capacity"):
            cb.submit({"tokens": [1, 2, 3], "maxNewTokens": 45})
        with pytest.raises(ValueError, match="b >= 1"):
            cb.submit({"tokens": np.zeros((0, 4), np.int32).tolist()})
        with pytest.raises(ValueError, match="maxNewTokens"):
            cb.submit({"tokens": [1, 2], "maxNewTokens": 0})
        # KV headroom: two admissible requests whose combined pages
        # exceed the pool shed the SECOND while it is still queued
        rng = np.random.RandomState(1)
        pa = rng.randint(1, 40, (1, 12)).astype(np.int32)
        outA = [None]
        got429 = []

        def first():
            outA[0] = cb.submit({"tokens": pa[0].tolist(),
                                 "maxNewTokens": 30}, timeout=120)

        ta = threading.Thread(target=first)
        ta.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not got429:
            try:
                cb.submit({"tokens": pa[0].tolist(),
                           "maxNewTokens": 30}, timeout=120)
                break                          # pool drained: admitted
            except ServiceOverloaded as e:
                got429.append(e.retryAfter)
                break
        ta.join(timeout=120)
        np.testing.assert_array_equal(outA[0], lm.generate(pa, 30))
        if got429:                             # shed carried a real hint
            assert got429[0] > 0
            assert serving_metrics().shed().value(
                model="cb-shed", rule="serving_kv_exhausted") >= 1
    finally:
        cb.shutdown()


def test_enqueue_rejection_is_offender_only():
    """Oversized prompts / impossible quotas / zero-row batches 400 at
    ``submit``, while a neighbour is mid-decode, and the neighbour's
    tokens come out untouched: an offender never poisons the shared
    batch (ISSUE 15 satellite)."""
    lm = _lm(layers=1, maxLen=64)
    cb = ContinuousBatcher(lm, name="cb-reject", pageSize=8,
                           maxSlots=2).start()
    try:
        p = np.random.RandomState(3).randint(1, 40, (1, 9)).astype(np.int32)
        stream = cb.submitStream({"tokens": p[0].tolist(),
                                  "maxNewTokens": 20})
        got = [next(stream)]                    # admitted and decoding
        with pytest.raises(ValueError, match="exceeds the top bucket"):
            cb.submit({"tokens": list(range(1, 36))})   # top bucket 32
        with pytest.raises(ValueError, match="capacity"):
            cb.submit({"tokens": [1, 2, 3], "maxNewTokens": 60})
        with pytest.raises(ValueError, match="b >= 1"):
            cb.submit({"tokens": np.zeros((0, 4), np.int32).tolist()})
        got.extend(stream)
        np.testing.assert_array_equal(np.asarray(got).ravel(),
                                      lm.generate(p, 20)[0])
    finally:
        cb.shutdown()
    # ForwardServing shares the zero-row guard
    from deeplearning4j_tpu.remote import ForwardServing
    fs = ForwardServing(object(), inputShape=(4,))
    with pytest.raises(ValueError, match="at least one row"):
        fs.makeRequest(np.zeros((0, 4), np.float32))


def test_step_failure_recovers_and_timeout_reaps():
    """A dispatch failure mid-step errors the affected sequences and the
    scheduler thread SURVIVES (pools rebuilt — the failed call may have
    consumed the donated buffers — and re-warmed); a timed-out submit
    reaps its queued rows instead of leaving phantom backlog."""
    from deeplearning4j_tpu.remote import BucketLadder
    lm = _lm(layers=1)
    cb = ContinuousBatcher(lm, name="cb-fail", pageSize=8, maxSlots=2,
                           ladder=BucketLadder(batchSizes=(2,),
                                               seqLens=(16,))).start()
    try:
        real = cb._stepFns["step"]
        state = {"n": 0}

        def bad(*a, **k):
            state["n"] += 1
            if state["n"] == 1:
                raise RuntimeError("injected device failure")
            return real(*a, **k)

        cb._stepFns["step"] = bad
        p = np.random.RandomState(0).randint(1, 40, (1, 8)
                                             ).astype(np.int32)
        with pytest.raises(RuntimeError, match="injected"):
            cb.submit({"tokens": p[0].tolist(), "maxNewTokens": 5},
                      timeout=60)
        out = cb.submit({"tokens": p[0].tolist(), "maxNewTokens": 5},
                        timeout=60)            # recovered, still exact
        np.testing.assert_array_equal(out, _lm(layers=1).generate(p, 5))
        # timeout reap: no phantom queued rows afterwards
        with pytest.raises(TimeoutError):
            cb.submit({"tokens": p[0].tolist(), "maxNewTokens": 20},
                      timeout=1e-4)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and (cb.queuedRows() or
                                               cb.busy()):
            time.sleep(0.02)
        assert cb.queuedRows() == 0
    finally:
        cb.shutdown()


# ----------------------------------------------- replica fan-out ------

def test_tp_replica_serves_through_registry_with_streaming():
    """A ShardingPlan-TP replica partitioned over 2 proxy devices serves
    through the same ModelRegistry route, bit-identical to the
    unsharded model — plus HTTP streaming and HTTP 400 routing."""
    import jax
    from deeplearning4j_tpu.parallel.mesh import DeviceMesh
    from deeplearning4j_tpu.parallel.meshtrainer import ShardingPlan
    from deeplearning4j_tpu.remote import BucketLadder
    ref_lm = _lm(layers=1)
    rng = np.random.RandomState(0)
    p = rng.randint(1, 40, (1, 10)).astype(np.int32)
    ref = ref_lm.generate(p, 8)
    lm = _lm(layers=1)
    plan = ShardingPlan(DeviceMesh(data=1, model=2,
                                   devices=jax.devices()[:2]),
                        tensorParallel=True)
    cb = ContinuousBatcher(lm, name="tp", pageSize=8, maxSlots=2,
                           plan=plan,
                           ladder=BucketLadder(batchSizes=(2,),
                                               seqLens=(16,)))
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(lm.params)}
    assert max(spans) >= 2                    # genuinely partitioned
    reg = ModelRegistry()
    reg.register("tp", cb)
    srv = InferenceServer(reg, port=0).start()
    try:
        _, out = _post(srv.port, "/v1/serving/tp",
                       {"tokens": p[0].tolist(), "maxNewTokens": 8})
        np.testing.assert_array_equal(np.asarray(out["tokens"]), ref)
        # streaming: NDJSON lines, one token per decode step
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/serving/tp",
            data=json.dumps({"tokens": p[0].tolist(), "maxNewTokens": 6,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.headers.get("Content-Type") == \
                "application/x-ndjson"
            lines = [json.loads(line) for line in resp]
        assert [ln["token"] for ln in lines if "token" in ln] == \
            ref[0][:6].tolist()
        assert lines[-1] == {"done": True}
        # enqueue-time rejection travels as HTTP 400 with the reason
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/v1/serving/tp",
                  {"tokens": [1, 2, 3], "maxNewTokens": 1000})
        assert ei.value.code == 400
        assert "capacity" in json.loads(ei.value.read())["error"]
        # stream:true against a non-streaming executor is an explicit
        # 400, never a silently different response shape
        class _NoStream:
            name = "nostream"

            def start(self):
                return self

            def submit(self, payload, timeout=None):
                return np.zeros((1, 1), np.int32)

            def queuedRows(self):
                return 0

            def shutdown(self):
                pass
        reg.register("nostream", _NoStream())
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/v1/serving/nostream",
                  {"tokens": [1], "maxNewTokens": 1, "stream": True})
        assert ei.value.code == 400
        assert "streaming" in json.loads(ei.value.read())["error"]
    finally:
        srv.stop()


def test_dp_replica_fanout_scales_on_queue_depth_edges():
    """ReplicaSet: DP replicas placed per device serve identically; the
    serving_queue_depth rule's FIRING edge scales one replica up and the
    RESOLVED edge scales back down, both counted in
    dl4j_tpu_health_actions_total."""
    import jax
    from deeplearning4j_tpu.telemetry.health import HealthMonitor
    rng = np.random.RandomState(0)
    p = rng.randint(1, 40, (1, 10)).astype(np.int32)
    ref = _lm(layers=1).generate(p, 6)
    devices = jax.devices()

    from deeplearning4j_tpu.remote import BucketLadder

    def factory(idx):
        m = _lm(layers=1)
        return ContinuousBatcher(m, name=f"dp/{idx}", pageSize=8,
                                 maxSlots=2,
                                 ladder=BucketLadder(batchSizes=(2,),
                                                     seqLens=(16,)),
                                 device=devices[idx % len(devices)])

    rs = ReplicaSet(factory, name="dp", replicas=1, maxReplicas=3)
    rs.start()
    try:
        np.testing.assert_array_equal(
            rs.submit({"tokens": p[0].tolist(), "maxNewTokens": 6},
                      timeout=120), ref)
        mon = HealthMonitor(rules=[])
        rs.armAutoscale(mon, highQueueRows=3)
        # REAL backlog (the rule reads live queued rows — a gauge
        # written at submit completion is blind during a cold burst):
        # 8 requests against 2 slots leaves >= 3 queued
        threads = [threading.Thread(target=lambda: rs.submit(
            {"tokens": p[0].tolist(), "maxNewTokens": 30}, timeout=120))
            for _ in range(8)]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and rs.queuedRows() < 3:
            time.sleep(0.005)
        assert rs.queuedRows() >= 3
        mon.evaluate_once(now=100.0)
        assert rs.replicaCount() == 2          # firing edge: +1 replica
        for th in threads:
            th.join(timeout=120)
        assert rs.queuedRows() == 0
        mon.evaluate_once(now=200.0)           # backlog gone: resolves
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and rs.replicaCount() != 1:
            time.sleep(0.05)
        assert rs.replicaCount() == 1          # resolved edge: -1
        # the surviving replica still serves
        np.testing.assert_array_equal(
            rs.submit({"tokens": p[0].tolist(), "maxNewTokens": 6},
                      timeout=120), ref)
        acted = get_registry().get("dl4j_tpu_health_actions_total")
        cells = dict((tuple(k), v) for k, v in acted.data()["cells"])
        assert cells.get(("serving_queue_depth_high", "ok"), 0) >= 2
    finally:
        rs.shutdown()


# ------------------------------------------------------- slow soak ----

@pytest.mark.slow
def test_ragged_arrival_soak_occupancy_and_flat_misses():
    """Sustained ragged traffic: decode-slot occupancy stays >= 0.9
    while demand exists, the jit-miss counter stays flat across ~dozens
    of admit/retire cycles, and every result is bit-identical."""
    lm = _lm(layers=1)
    ref_lm = _lm(layers=1)
    cb = ContinuousBatcher(lm, name="cb-soak", pageSize=8,
                           maxSlots=4).start()
    try:
        rng = np.random.RandomState(0)
        seen = cb.compileCacheSize()
        n = 32
        prompts = [rng.randint(1, 40, (1, int(rng.randint(3, 30)))
                               ).astype(np.int32) for _ in range(n)]
        quotas = [int(rng.randint(4, 14)) for _ in range(n)]
        outs = [None] * n

        def run(i):
            outs[i] = cb.submit({"tokens": prompts[i][0].tolist(),
                                 "maxNewTokens": quotas[i]}, timeout=300)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert all(not th.is_alive() for th in threads)
        for i in range(n):
            np.testing.assert_array_equal(
                outs[i], ref_lm.generate(prompts[i], quotas[i]))
        assert cb.compileCacheSize() == seen          # flat across churn
        assert cb.occupancy() is not None and cb.occupancy() >= 0.9
        assert cb.pool.freePages() == cb.pool.numPages - 1
    finally:
        cb.shutdown()
