"""The Jamba served LM (``nlp/jamba.py``: Mamba-1 layers with normalised
Δ/B/C, one multi-query attention layer a period) and the Mamba mixer it
shares with SambaY (``nlp/mamba.py``) against the benchmark's plain
reference, at a small size on the CPU: the mixer's two forms against the
token-by-token recurrence, the full forward on logits, then prefill +
decode through the scheduler's cache manager holding pages and a
recurrent state side by side.

The reference is ``benchmark/references/jamba.py`` itself, loaded by
path: it imports nothing of the program, so the benchmark stays
independent of what it is compared with.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from test_sambay import _load, _teacher_forced

pytestmark = pytest.mark.cbatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# two periods of four: mamba, mamba, attention, mamba; four query heads on
# ONE KV head
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 1, "intermediate_size": 128,
        "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_expand": 2,
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 4,
        "num_hidden_layers": 8, "vocab_size": 96, "rms_norm_eps": 1e-6,
        "num_experts": 1, "mamba_proj_bias": False, "mamba_conv_bias": True,
        "tie_word_embeddings": True, "sliding_window": None}
PAGE, SLOTS, CAP = 4, 3, 64

# float32 weights on the CPU: both sides compute in float32 and differ in
# the order of their sums -- measured 2.4e-7 on logits up to 1.2
TOL_F32 = 2e-5
# bfloat16 weights: the program rounds the residual stream, K/V and every
# matmul's input to 8 bits of mantissa at each of 8 layers where the
# reference keeps float32; measured 0.0042, and float8 inputs read 0.037
TOL_BF16 = 0.02


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/references/jamba.py", "bench_ref_jamba")


@pytest.fixture(scope="module")
def family():
    return _load("benchmark/configs/jamba.py", "bench_cfg_jamba")


@pytest.fixture(scope="module")
def weights(ref):
    import jax
    return ref.make_weights(TINY, jax.random.PRNGKey(3))


def _lm(family, weights, dtype):
    import jax
    cfg = dict(TINY, dtype=dtype)
    return family.build_lm(cfg, jax.tree.map(lambda a: a.astype(dtype),
                                             weights), CAP)


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, TINY["vocab_size"], size=n).tolist()
            for n in lengths]


# -- the shared mixer against the recurrence ---------------------------------
@pytest.mark.parametrize("norms", [True, False],
                         ids=["inner_norms", "no_inner_norms"])
def test_shared_mamba_mixer_is_the_plain_recurrence(ref, family, weights,
                                                    norms):
    """``nlp/mamba.py`` in both its forms against the reference's mixer
    (the plain ``lax.scan`` over positions), with the three inner norms
    (Jamba) and without (SambaY): ``mamba_full`` over two LEFT-padded
    sequences, one of them shorter than the convolution's three rows, then
    ``mamba_step`` carrying the state and the window it returned through
    six more tokens, a third slot kept idle."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.mamba import mamba_full, mamba_step
    D = ref.dims(TINY)
    p = weights["layers"][0]["mixer"]
    lp = family.to_program({"emb": None, "norm_f": None,
                            "layers": [weights["layers"][0]]})["layers"][0]
    lp = {n: jnp.asarray(a, jnp.float32) for n, a in lp.items()}
    kw = dict(N=D["N"], R=D["R"], eps=D["eps"] if norms else None)
    rs = np.random.RandomState(4)
    T, more, lengths = 12, 6, (9, 2)
    h = rs.randn(2, T + more, D["d"]).astype(np.float32)
    want = [np.asarray(ref.mamba(jnp.asarray(h[i, T - n:]), p, D, False,
                                 norms=norms))
            for i, n in enumerate(lengths)]
    real = (np.arange(T)[None, :] >= T - np.asarray(lengths)[:, None])
    out, _, s, tail = mamba_full(lp, jnp.asarray(h[:, :T]),
                                 jnp.asarray(real[..., None], jnp.float32),
                                 K=D["K"], **kw)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(out[i, T - n:]), want[i][:n],
                                   atol=2e-6)
    # the step form from there, beside an idle third slot
    s = jnp.concatenate([s, jnp.full_like(s[:1], 7.0)])
    win = jnp.concatenate([tail, jnp.full_like(tail[:1], 7.0)])
    active = jnp.asarray([True, True, False])
    keep = lambda new, old: jnp.where(
        active.reshape((3,) + (1,) * (new.ndim - 1)), new, old)
    for t in range(more):
        ht = jnp.concatenate([jnp.asarray(h[:, T + t]),
                              jnp.zeros((1, D["d"]), jnp.float32)])
        out, _, s, win = mamba_step(lp, ht, s, win, keep, **kw)
        for i, n in enumerate(lengths):
            np.testing.assert_allclose(np.asarray(out[i]), want[i][n + t],
                                       atol=2e-6)
    assert float(s[2].min()) == float(win[2].max()) == 7.0


@pytest.mark.parametrize("heads,kvHeads", [(4, 1), (4, 2), (4, 4)])
def test_blocked_full_attention_is_plain_grouped_softmax(heads, kvHeads):
    """``nlp/served.py:attend_full`` (Olmo-Hybrid's prefill is its one-
    query-head-a-KV-head case, Jamba's its one-KV-head case) against the
    plain softmax over every key: two blocks of queries, left padding,
    query head ``a`` on KV head ``a // (heads / kvHeads)``."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.served import QUERY_BLOCK, attend_full
    b, T, dh = 2, 2 * QUERY_BLOCK, 8
    rng = np.random.default_rng(heads * 10 + kvHeads)
    q = rng.standard_normal((b, T, heads * dh)).astype(np.float32)
    k = rng.standard_normal((b, T, kvHeads * dh)).astype(np.float32)
    v = rng.standard_normal((b, T, kvHeads * dh)).astype(np.float32)
    start = np.asarray([0, 700], np.int32)
    got = np.asarray(attend_full(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), jnp.asarray(start),
                                 nHeads=heads, nKvHeads=kvHeads))
    kv = lambda a: np.repeat(a.reshape(b, T, kvHeads, dh),
                             heads // kvHeads, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q.reshape(b, T, heads, dh),
                  kv(k)) / np.sqrt(dh)
    pos = np.arange(T)
    valid = (pos[None, :] <= pos[:, None])[None] \
        & (pos[None, None, :] >= start[:, None, None])       # (b, q, k)
    s = np.where(valid[:, None], s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True, initial=-1e30))
    a = a / np.maximum(a.sum(-1, keepdims=True), 1e-30)
    want = np.einsum("bhqk,bkhd->bqhd", a, kv(v)).reshape(b, T, heads * dh)
    real = pos[None, :] >= start[:, None]                    # pad rows: any
    assert np.abs(got - want)[real].max() < 2e-5


def test_layer_kinds_follow_the_published_pattern(ref, family):
    kinds = family.program_config(TINY, CAP).layerKinds()
    assert kinds == ["mamba", "mamba", "attention", "mamba"] * 2 \
        == ref.layer_kinds(TINY)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        kinds = ref.layer_kinds(json.load(f))
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32),
                                       ("bfloat16", TOL_BF16)])
def test_full_forward_matches_the_reference_logits(ref, family, weights,
                                                   dtype, tol):
    import jax
    lm = _lm(family, weights, dtype)
    w = jax.tree.map(lambda a: a.astype(dtype), weights)
    toks = _prompts([29])[0]
    want = np.asarray(ref.logits(TINY, w, toks))
    got = np.asarray(lm.forward(np.asarray([toks])))[0]
    assert got.dtype == np.float32
    assert np.abs(got - want).max() < tol
    if dtype == "bfloat16":
        # the tolerance separates the stated precision from the one below
        low = np.asarray(ref.logits(TINY, w, toks, low=True))
        assert np.abs(low - want).max() > tol


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32),
                                       ("bfloat16", TOL_BF16)])
def test_prefill_and_paged_decode_match_the_reference_logits(
        ref, family, weights, dtype, tol):
    """Logits of every decode step, teacher-forced, through the pool's
    pages and recurrent state: a ragged left-padded prompt, 40 new tokens,
    then THE SAME SLOT reused by a prompt of TWO tokens (shorter than the
    convolution's three rows: the window it leaves holds a zero row) whose
    stale pages, state and window must not reach it, then by a longer one
    in another bucket; the idle slots' state is left as it was."""
    import jax
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    w = jax.tree.map(lambda a: a.astype(dtype), weights)
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    write = lm.buildPagedPrefillWriteFn()
    step = jax.jit(lm.pagedLogits)
    idle = [np.asarray(a[:, 0]).copy() for a in pool.arrays[2:]]
    for prompt, bucket in ((_prompts([11])[0], 16), (_prompts([2], 2)[0], 8),
                           (_prompts([13], 3)[0], 16)):
        forced = _prompts([40], seed=len(prompt))[0]
        seq = prompt + forced
        want = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1))
        got = np.stack(list(_teacher_forced(lm, pool, write, step, 1, prompt,
                                            bucket, forced)))
        assert np.abs(got - want).max() < tol
        assert pool.release(1) == -(-(bucket + 40) // PAGE)
    # slot 0 never held a sequence: the steps left its state untouched
    for before, a in zip(idle, pool.arrays[2:]):
        np.testing.assert_array_equal(before, np.asarray(a[:, 0]))
    assert pool.usedPages() == 0 and pool.stateSlots() == 0


@pytest.fixture
def batcher(family, weights):
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="jamba", maxSlots=SLOTS,
        pageSize=PAGE, numPages=1 + SLOTS * (CAP // PAGE),
        ladder=BucketLadder(batchSizes=(SLOTS,), seqLens=(8, 16)))
    cb.start()
    yield cb
    cb.shutdown()


def _served_gap(ref, weights, prompt, served):
    """How far the served tokens' reference logits lie below the
    reference's best, at their worst."""
    import jax
    w = jax.tree.map(lambda a: a.astype("float32"), weights)
    lg = np.asarray(ref.logits(TINY, w, (prompt + served)[:-1],
                               first=len(prompt) - 1))
    return float((lg.max(-1) - lg[np.arange(len(served)), served]).max())


def test_continuous_batcher_serves_the_reference_tokens(ref, weights,
                                                        batcher):
    """Six ragged prompts in two buckets on three slots (two of them
    shorter than the convolution's three rows), sent at different moments,
    40 new tokens each: sequences are admitted at different steps beside
    running neighbours and every slot is reused after a retirement.  Every
    served token must be the reference's best up to float32 rounding of
    logits (``TOL_F32``); then the manager's books are empty."""
    prompts = _prompts([5, 11, 16, 2, 1, 7])
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
    # pages for the TWO attention layers, a row the ONE KV head's 16 lanes;
    # the six Mamba layers' state and windows beside them, and no ring
    assert pool.k.shape == (2, pool.numPages, PAGE, 16)
    assert [a.shape for a in pool.arrays[2:]] == [
        (6, SLOTS, 4, 128), (6, SLOTS, 3, 128)]
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert pool.freePages() == pool.numPages - 1


def test_serving_telemetry_covers_the_model_unchanged(ref, weights, batcher):
    """The spans and every ``dl4j_tpu_serving_*`` series expose under the
    batcher's name with no line written for this model: pages and
    recurrent state in ``cache_bytes{kind}``, the ring series at 0 for a
    model without window layers, the drain clock's histogram, the padded
    and the real prompt positions by bucket, and, here on the CPU, the
    kernel's gauge at 0 (1 on one TPU: ``tests/test_tpu_compile.py``)."""
    from deeplearning4j_tpu.telemetry import serving_metrics, tracer
    sm = serving_metrics()
    count = lambda c, **kw: c.value(model="jamba", **kw) or 0
    before = (count(sm.prefill_prompt_tokens()),
              sum(count(sm.prefill_positions(), bucket=str(b))
                  for b in (8, 16)), count(sm.decode_steps()))
    seen = {}

    def held():                     # while a sequence decodes
        seen["paged"] = sm.cache_bytes().value(model="jamba", kind="paged")
        seen["recurrent"] = sm.cache_bytes().value(model="jamba",
                                                   kind="recurrent")
        seen["slots"] = sm.state_slots_in_use().value(model="jamba")
    stream = batcher.submitStream({"tokens": _prompts([11])[0],
                                   "maxNewTokens": 30})
    toks = [next(stream) for _ in range(5)]
    held()
    toks.extend(stream)
    other = np.asarray(batcher.submit(
        {"tokens": _prompts([3], 2)[0], "maxNewTokens": 6}))[0].tolist()
    assert len(toks) == 30 and len(other) == 6
    assert seen["slots"] == 1 and seen["paged"] > 0
    # one slot's share of the six layers' float32 state and bf16-or-f32
    # windows, as the pool counts them
    pool = batcher.pool
    assert seen["recurrent"] == sum(
        a.nbytes // SLOTS for a in pool.arrays[2:])
    names = {e["name"] for e in tracer().events()}
    assert {"serving.prefill", "serving.state.write", "serving.decode.step",
            "serving.loop.fetch", "serving.loop.dispatch"} <= names
    assert sm.state_slots_in_use().value(model="jamba") == 0
    assert sm.cache_bytes().value(model="jamba", kind="paged") == 0
    assert sm.cache_bytes().value(model="jamba", kind="recurrent") == 0
    assert sm.cache_bytes().value(model="jamba", kind="ring") == 0
    assert sm.ring_rows_in_use().value(model="jamba") == 0
    assert sm.paged_attention_kernel().value(model="jamba") == 0
    assert sm.paged_attention_kv_passes().value(model="jamba") == 0
    assert sm.ring_attention_kernel().value(model="jamba") == 0
    assert sm.moe_step_kernel().value(model="jamba") == 0
    assert sm.moe_grouped_kernel().value(model="jamba") == 0
    assert sm.slot_occupancy().value(model="jamba") is not None
    assert count(sm.decode_steps()) - before[2] >= 29
    assert count(sm.prefill_prompt_tokens()) - before[0] == 11 + 3
    assert sum(count(sm.prefill_positions(), bucket=str(b))
               for b in (8, 16)) - before[1] == 16 + 8
    idle = sm.device_idle_seconds()
    assert sum(idle.count(model="jamba", cause=c) or 0
               for c in ("wait", "admit", "loop")) > 0


def test_preempt_replay_and_evacuate_return_the_same_tokens(ref, weights,
                                                            batcher):
    """A preempted sequence restarts from its prompt: prefill rebuilds
    pages, state and convolution window, the replay is teacher-forced, and
    the client sees each token once.  ``evacuate`` hands the sequences
    over reset the same way."""
    from deeplearning4j_tpu.remote.scheduler import _Seq
    prompts = _prompts([9, 2], seed=7)
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


def test_admission_behind_an_unread_step_that_wrote_the_slots_state(
        ref, weights, batcher):
    """The loop is one step ahead, and Y is admitted into X's slot while
    a step that wrote X's state, convolution window and page row is still
    unread (X's quota ends: its slot is free from the dispatch of its last
    step); device order puts Y's admission write behind it, so Y and its
    neighbours get the reference's tokens and the books are empty.  Y is
    DEFERRED a round on the way (the pool is squeezed when its next page
    is due: its ``pos`` goes out as 0 for that step, which must leave its
    state and window as they are).  Iterated by hand, so no clock decides
    what is unread when."""
    with batcher._cv:
        batcher._running = False
        batcher._cv.notify_all()
    batcher._thread.join(10)
    assert not batcher._thread.is_alive()
    batcher._thread, batcher._running = None, True
    pa, px, pz, py = _prompts([9, 6, 13, 2], seed=5)

    def stream(prompt, n=30):
        gen = batcher.submitStream({"tokens": prompt, "maxNewTokens": n})
        return gen, batcher._queue[-1]
    # 5 tokens: one from the prefill, the last from the fourth step
    (ga, sa), (gx, sx), (gz, sz) = stream(pa), stream(px, 5), stream(pz)
    for _ in range(4):
        batcher._iterate()
    assert batcher._inflight.seqs == [sa, sx, sz]
    assert batcher._parted == [sx]
    gy, sy = stream(py)
    assert batcher._slotSeq == [sa, None, sz]
    batcher._iterate()          # Y's admission, behind that unread step
    assert batcher._slotSeq == [sa, sy, sz]
    # Y, the youngest, sits out a round: no page is free when it asks for
    # its next, and there is nobody younger to preempt
    pool = batcher.pool
    slotY = batcher._slotSeq.index(sy)
    deferred = []
    ensure = pool.ensure

    def squeezed(slot, n):
        if slot == slotY and not deferred and n > len(
                pool.heldIds(slot)) * PAGE:
            deferred.append(n)
            return False
        return ensure(slot, n)
    pool.ensure = squeezed
    while not batcher._idle():
        batcher._iterate()
    pool.ensure = ensure
    assert deferred and all(q.restarts == 0 for q in (sa, sx, sz, sy))
    for p, g, n in ((pa, ga, 30), (pz, gz, 30), (py, gy, 30), (px, gx, 5)):
        toks = list(g)
        assert len(toks) == n
        assert _served_gap(ref, weights, p, toks) < TOL_F32
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert batcher._inflight is None and batcher._parted == []


def test_each_prompt_bucket_prefills_under_its_own_name(family, weights):
    """The device trace tells a bucket's prefill from another's by the
    program's name, which ``prefill_mfu_pct.assist`` counts operations by
    (``benchmark/readers/prefill_mfu.py``); the batcher counts the jits
    as it counted the one."""
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
    """``jax.eval_shape`` of the published sizes: 3,029,337,472 parameters
    (the model card says 3 B), nothing cut, every width as published; the
    pool's spec is two paged layers of ONE 128-lane head and 26 states."""
    import jax
    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        config = json.load(f)
    assert config["reduced"] == []
    empty = {"emb": None, "norm_f": None, "layers": []}
    lm = family.build_lm(config, empty, config["serving"]["capacity"])
    kinds = lm.config.layerKinds()
    assert (kinds.count("mamba"), kinds.count("attention")) == (26, 2)
    shapes = jax.eval_shape(lm._init_params)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == ref.param_count(config) == 3_029_337_472
    assert ref.layer_params(config) == {"mamba": 104_161_472,
                                        "attention": 76_682_240}
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(shapes))
    spec = lm.cacheSpec()
    assert (spec.pagedLayers, spec.kvHeads, spec.headSize, spec.ringLayers,
            spec.rowWidth) == (2, 1, 128, 0, 128)
    assert [(n, s, np.dtype(t)) for n, s, t in spec.slotState] == [
        ("ssm", (26, 16, 5120), np.dtype("float32")),
        ("conv", (26, 3, 5120), np.dtype("bfloat16"))]
