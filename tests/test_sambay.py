"""The SambaY served LM (``nlp/sambay.py``: Mamba, window and full
differential attention, cross-attention onto one shared KV layer, GMUs)
against the benchmark's plain reference, on logits, at a small size on the
CPU: the full forward, then prefill + decode through the scheduler's cache
manager holding its three kinds of state.

The reference is ``benchmark/references/phi4flash.py`` itself, loaded by
path: it imports nothing of the program, so the benchmark stays
independent of what it is compared with.
"""
import importlib.util
import os
import sys
import threading
import time

import numpy as np
import pytest

pytestmark = pytest.mark.cbatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Mamba, window, Mamba, window, Mamba-with-memory, full, GMU, cross
TINY = {"hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "sliding_window": 8, "mb_per_layer": 2, "mamba_expand": 2,
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 4,
        "num_hidden_layers": 8, "vocab_size": 96, "layer_norm_eps": 1e-5}
PAGE, SLOTS, CAP = 4, 3, 64

# float32 weights on the CPU: both sides compute in float32 and differ in
# the order of their sums (the program contracts K rows 2*dh wide, the
# reference per head) -- a few ulp of logits whose spread is 0.17
TOL_F32 = 2e-5
# bfloat16 weights: the program rounds the residual stream, q/k/v and the
# matmul inputs to 8 bits of mantissa at each of 8 layers where the
# reference keeps float32; measured 0.004, and float8 inputs read 0.05
TOL_BF16 = 0.02


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
    return _load("benchmark/references/phi4flash.py", "bench_ref_phi4flash")


@pytest.fixture(scope="module")
def family():
    return _load("benchmark/configs/phi4flash.py", "bench_cfg_phi4flash")


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


def test_layer_kinds_follow_the_published_pattern(ref, family):
    kinds = family.program_config(TINY, CAP).layerKinds()
    assert kinds == ["mamba", "window", "mamba", "window", "mamba", "full",
                     "gmu", "cross"] == ref.layer_kinds(TINY)


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32),
                                       ("bfloat16", TOL_BF16)])
def test_full_forward_matches_the_reference_logits(ref, family, weights,
                                                   dtype, tol):
    import jax
    lm = _lm(family, weights, dtype)
    w = jax.tree.map(lambda a: a.astype(dtype), weights)
    toks = _prompts([24])[0]                    # three windows long
    want = np.asarray(ref.logits(TINY, w, toks))
    got = np.asarray(lm.forward(np.asarray([toks])))[0]
    assert got.dtype == np.float32
    assert np.abs(got - want).max() < tol
    if dtype == "bfloat16":
        # the tolerance separates the stated precision from the one below
        low = np.asarray(ref.logits(TINY, w, toks, low=True))
        assert np.abs(low - want).max() > tol


def _teacher_forced(lm, pool, write, step, slot, prompt, bucket, forced):
    """Admit ``prompt`` into ``slot`` (left-padded to ``bucket``) and feed
    ``forced`` one token a step; yields each step's logits for the slot
    while the OTHER slots stay idle (``pos`` 0)."""
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
        pool.arrays = out[1:]
        logits = np.asarray(out[0][slot, 0])    # the step has ended: only
        pos[slot] += 1                          # now may its inputs change
        yield logits


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32),
                                       ("bfloat16", TOL_BF16)])
def test_prefill_and_paged_decode_match_the_reference_logits(
        ref, family, weights, dtype, tol):
    """Logits of every decode step, teacher-forced, through the pool's
    three kinds of state: a ragged left-padded prompt, 40 new tokens (five
    wraps of the ring), then THE SAME SLOT reused by a shorter sequence in
    another bucket whose stale ring rows, pages and recurrent state must
    not reach it; the idle slots' state is left as it was."""
    import jax
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    w = jax.tree.map(lambda a: a.astype(dtype), weights)
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    write = lm.buildPagedPrefillWriteFn()
    step = jax.jit(lm.pagedLogits)
    idle = [np.asarray(a[:, 0]).copy() for a in pool.arrays[2:]]
    for prompt, bucket in ((_prompts([11])[0], 16), (_prompts([5], 2)[0], 8)):
        forced = _prompts([40], seed=len(prompt))[0]
        seq = prompt + forced
        want = np.asarray(ref.logits(TINY, w, seq, first=len(prompt) - 1))
        got = np.stack(list(_teacher_forced(lm, pool, write, step, 1, prompt,
                                            bucket, forced)))
        assert np.abs(got - want).max() < tol
        assert pool.release(1) == -(-(bucket + 40) // PAGE)
    # slot 0 never held a sequence: the steps left its ring rows and
    # recurrent state untouched
    for before, a in zip(idle, pool.arrays[2:]):
        np.testing.assert_array_equal(before, np.asarray(a[:, 0]))
    assert pool.usedPages() == 0 and pool.stateSlots() == 0


# (start, pos) of the three slots, at the window of 8 rows: ``pos`` is the
# position this step writes and reads up to
RING_CASES = {
    "not_yet_full": [(0, 5), (0, 3), (0, 1)],
    # wrapped in absolute positions (pos >= W) but not yet W rows long:
    # start 100, pos 520 at a window of 512
    "not_yet_full_left_padded_and_wrapped": [(3, 9), (5, 11), (2, 8)],
    "exactly_full": [(0, 7), (3, 10), (1, 8)],
    "wrapped_several_times": [(0, 29), (3, 40), (5, 23)],
    "idle_slot_beside_live_ones": [(0, 0), (2, 12), (0, 6)],
}


@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_step_reads_its_ring_as_the_old_rule_read_it(family, weights, case):
    """A window layer's step reads its ring through
    ``paged_attention_read`` (here the gathered lowering), position ``p``
    at ring row ``(p - start) % W`` and the live rows one interval.  It
    must see the same rows as ``_diff_attend`` saw under the rule it
    replaces (``p`` at row ``p % W``, a row valid iff the position it
    holds is ``>= start``), stale rows of a slot's earlier tenant around
    them; a slot whose ``pos`` is 0 keeps its ring rows, and in the whole
    step its recurrent state, as they were."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, "float32")
    c = lm.config
    W, w = c.window, c.nKvHeads * c.headSize
    i = [n for n, k in enumerate(c.layerKinds()) if k == "window"][1]
    lp, wi = lm.params["layers"][i], 1
    start, pos = (np.asarray(x, np.int32) for x in zip(*RING_CASES[case]))
    live = pos > 0
    rs = np.random.RandomState(sorted(RING_CASES).index(case))
    T = int(pos.max()) + 1
    kv = rs.randn(2, SLOTS, T, w).astype(np.float32)
    q = rs.randn(SLOTS, 1, c.nHeads * c.headSize).astype(np.float32)
    old = rs.randn(2, SLOTS, W, w).astype(np.float32)       # stale rows
    new = rs.randn(2, 2, SLOTS, W, w).astype(np.float32)    # (K/V, layers)
    for s in np.flatnonzero(live):
        for p in range(start[s], pos[s] + 1):
            old[:, s, p % W] = kv[:, s, p]
            if p < pos[s]:
                new[:, wi, s, (p - start[s]) % W] = kv[:, s, p]
    before = new.copy()
    r = np.arange(W)[None, :]
    held = pos[:, None] - (pos[:, None] - r) % W
    want = np.asarray(lm._diff_attend(
        lp, i, jnp.asarray(q), jnp.asarray(old[0]), jnp.asarray(old[1]),
        jnp.asarray(held >= start[:, None])[:, None]))
    rows = kv[:, np.arange(SLOTS), pos]                     # this step's
    got, ringK, ringV = lm._ring_step(
        lp, i, jnp.asarray(q), jnp.asarray(rows[0]), jnp.asarray(rows[1]),
        jnp.asarray(new[0]), jnp.asarray(new[1]), wi, jnp.asarray(pos),
        jnp.asarray(start))
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got)[live], want[live],
                               rtol=2e-5, atol=2e-6)
    after = np.stack([np.asarray(ringK), np.asarray(ringV)])
    for s in range(SLOTS):
        wrote = (pos[s] - start[s]) % W
        for row in range(W):
            if live[s] and row == wrote:
                np.testing.assert_array_equal(after[:, wi, s, row], rows[:, s])
            else:
                np.testing.assert_array_equal(after[:, wi, s, row],
                                              before[:, wi, s, row])
    np.testing.assert_array_equal(after[:, 0], before[:, 0])
    if live.all():
        return
    # the whole step: an idle slot's rings and recurrent state stay put
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    for s in np.flatnonzero(live):
        assert pool.ensure(int(s), int(pos[s]) + 1)
    arrays = [jnp.asarray(rs.randn(*a.shape), a.dtype) for a in pool.arrays]
    out = jax.jit(lm.pagedLogits)(
        lm.params, *arrays, jnp.zeros((SLOTS, 1), jnp.int32),
        jnp.asarray(pool.pageTable), jnp.asarray(pos), jnp.asarray(start))
    idle = np.flatnonzero(~live)
    for a, b in zip(arrays[2:], out[3:]):
        np.testing.assert_array_equal(np.asarray(a)[:, idle],
                                      np.asarray(b)[:, idle])
        assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture
def batcher(family, weights):
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="sambay", maxSlots=SLOTS,
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
    """Five ragged prompts in two buckets on three slots, sent at
    different moments, 40 new tokens each: sequences are admitted at
    different steps beside running neighbours, every ring wraps five
    times, and two slots are reused after a retirement.  Every served
    token must be the reference's best up to float32 rounding of logits
    (``TOL_F32``); then the manager's books are empty."""
    from deeplearning4j_tpu.telemetry import serving_metrics, tracer
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
    # pages only for the ONE paged layer; every page and slot free again
    assert pool.k.shape == (1, pool.numPages, PAGE, 4 * 8)
    assert [a.shape[:2] for a in pool.arrays[2:]] == [
        (2, SLOTS), (2, SLOTS), (3, SLOTS), (3, SLOTS)]
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert pool.freePages() == pool.numPages - 1
    # every admission wrote its state under a span of its own
    assert any(e["name"] == "serving.state.write" for e in tracer().events())
    sm = serving_metrics()
    assert sm.state_slots_in_use().value(model="sambay") == 0
    assert sm.cache_bytes().value(model="sambay", kind="paged") == 0
    assert sm.cache_bytes().value(model="sambay", kind="ring") == 0
    assert sm.ring_rows_in_use().value(model="sambay") == 0
    # each of the five passes a multiple of the window four times or more
    assert sm.ring_wraps().value(model="sambay") >= 20


def test_paged_layer_is_read_through_the_gathered_lowering_here(family,
                                                               weights):
    """The step reads its paged layer and its rings through
    ``paged_attention_read``, which is lowered where the program is: here
    on the CPU the gathered reference, so the batcher's gauges read 0 (1 /
    1 / 1 on one TPU, where the eight readers of the paged layer and the
    two window layers go through the kernel:
    ``tests/test_tpu_compile.py``)."""
    from deeplearning4j_tpu.nn.conf.attention import paged_kernel_lowerings
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    from deeplearning4j_tpu.telemetry import serving_metrics
    sm = serving_metrics()
    sm.paged_attention_kernel().set(1, model="sambay-gauge")
    sm.paged_attention_kv_passes().set(1, model="sambay-gauge")
    sm.ring_attention_kernel().set(1, model="sambay-gauge")
    before = paged_kernel_lowerings()
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="sambay-gauge",
        maxSlots=SLOTS, pageSize=PAGE, numPages=1 + SLOTS * (CAP // PAGE),
        ladder=BucketLadder(batchSizes=(SLOTS,), seqLens=(8,)))
    try:
        cb.warm()
    finally:
        cb.shutdown()
    assert paged_kernel_lowerings() == before
    assert sm.paged_attention_kernel().value(model="sambay-gauge") == 0
    assert sm.paged_attention_kv_passes().value(model="sambay-gauge") == 0
    assert sm.ring_attention_kernel().value(model="sambay-gauge") == 0


def test_preempt_replay_and_evacuate_return_the_same_tokens(ref, weights,
                                                            batcher):
    """A preempted sequence restarts from its prompt: prefill rebuilds
    pages, rings and recurrent state, the replay is teacher-forced, and
    the client sees each token once.  ``evacuate`` hands the sequences
    over reset the same way."""
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
    # evacuate: two in flight, handed back reset for a replay from the
    # prompt with every page and slot released
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


@pytest.mark.parametrize("end", ["hangup", "quota"])
def test_admission_behind_an_unread_step_that_wrote_the_slots_state(
        ref, weights, batcher, end):
    """The loop is one step ahead, and Y is admitted into X's slot while
    a step that wrote X's ring rows, recurrent state and page row is
    still unread; device order puts Y's admission write behind it, so Y
    and its neighbours get the reference's tokens and the books are
    empty.  ``hangup``: X's end is learnt with one more step dispatched,
    whose token is discarded.  ``quota``: X's end is known ahead, its
    slot is free from the dispatch of its last step, and that step's
    token still reaches X.  Iterated by hand, so no clock decides what is
    unread when."""
    from deeplearning4j_tpu.telemetry import serving_metrics
    sm = serving_metrics()
    counts = lambda: np.asarray([c.value(model="sambay") for c in (
        sm.decode_tokens_discarded(), sm.decode_steps(),
        sm.decode_steps_overlapped())])
    before = counts()
    with batcher._cv:
        batcher._running = False
        batcher._cv.notify_all()
    batcher._thread.join(10)
    assert not batcher._thread.is_alive()
    batcher._thread, batcher._running = None, True
    pa, px, pz, py = _prompts([9, 6, 13, 7], seed=5)

    def stream(prompt, n=30):
        gen = batcher.submitStream({"tokens": prompt, "maxNewTokens": n})
        return gen, batcher._queue[-1]
    # 5 tokens: one from the prefill, the last from the fourth step
    (ga, sa), (gx, sx), (gz, sz) = \
        stream(pa), stream(px, 5 if end == "quota" else 30), stream(pz)
    for _ in range(4):
        batcher._iterate()
    assert batcher._inflight.seqs == [sa, sx, sz]
    if end == "quota":
        assert batcher._parted == [sx]
    else:
        assert batcher._slotSeq == [sa, sx, sz]
        next(gx)
        gx.close()                              # X's client hangs up
    gy, sy = stream(py)
    if end == "hangup":
        batcher._iterate()      # a step with X in it goes out, X retires
    assert batcher._slotSeq == [sa, None, sz]
    assert batcher._inflight.seqs[1] is sx
    batcher._iterate()          # Y's admission, behind that unread step
    assert batcher._slotSeq == [sa, sy, sz]
    while not batcher._idle():
        batcher._iterate()
    served = [(pa, ga, 30), (pz, gz, 30), (py, gy, 30)]
    if end == "quota":
        served.append((px, gx, 5))
    for p, g, n in served:
        toks = list(g)
        assert len(toks) == n
        assert _served_gap(ref, weights, p, toks) < TOL_F32
    discarded, steps, ahead = counts() - before
    assert discarded == (end == "hangup") and ahead == steps - 1
    pool = batcher.pool
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert batcher._inflight is None and batcher._parted == []


def test_published_configuration_counts_its_parameters(ref, family):
    """``jax.eval_shape`` of the published sizes: 3.85 B parameters (the
    model card says 3.8 B) in layers of kinds 9 / 8 / 1 / 7 / 7."""
    import json

    import jax
    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
        config = json.load(f)
    assert config["reduced"] == []
    lm = family.build_lm(config, {"emb": None, "ln_f": {"g": None, "b": None},
                                  "layers": []},
                         config["serving"]["capacity"])
    kinds = lm.config.layerKinds()
    assert [kinds.count(k) for k in ("mamba", "window", "full", "cross",
                                     "gmu")] == [9, 8, 1, 7, 7]
    shapes = jax.eval_shape(lm._init_params)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == ref.param_count(config) == 3_852_457_984
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(shapes))
    spec = lm.cacheSpec()
    assert (spec.pagedLayers, spec.ringLayers, spec.ringRows,
            spec.rowWidth) == (1, 8, 512, 1280)
    name, shape, dtype = spec.slotState[0]
    assert (name, shape, np.dtype(dtype)) == ("ssm", (9, 16, 5120),
                                              np.float32)


# -- the Mamba mixer moved to nlp/mamba.py (PR 38) ---------------------------
GOLDEN = os.path.join(REPO, "tests", "fixtures", "sambay_logits_pr37.npz")


def _golden(family, weights, dtype):
    """What ``fixtures/sambay_logits_pr37.npz`` holds for ``dtype``: the
    full forward's logits of a 24-token prompt, and the logits of a
    left-padded prefill (11 tokens in the 16 bucket) and of 12
    teacher-forced steps through the pool.  Recorded on commit 09ebd17
    (PR 37), where the mixer still lay in ``sambay.py``, by
    ``np.savez(GOLDEN, **{f"{form}_{dtype}": ...})`` over this function,
    with ``canary`` = ``ref.logits(TINY, weights, _prompts([24])[0])`` of
    the same machine.  The ``served_*`` arrays were recorded anew the
    same way in PR 39 (a machine whose canary is the file's), when the
    window layers' step came to read its ring through
    ``paged_attention_read``: the pair's difference is taken of two
    float32 contexts there, where ``_diff_attend`` takes it of the
    weights; the recording they replace is kept as ``served_*_pr37`` and
    the new one held to it within the file's tolerances."""
    import jax
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    forward = np.asarray(lm.forward(np.asarray([_prompts([24])[0]])))[0]
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    served = np.stack(list(_teacher_forced(
        lm, pool, lm.buildPagedPrefillWriteFn(), jax.jit(lm.pagedLogits), 1,
        _prompts([11])[0], 16, _prompts([12], seed=11)[0])))
    return {"forward": forward, "served": served}


@pytest.mark.parametrize("form", ["forward", "served"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_logits_are_bit_for_bit_what_they_were_before_the_mixer_moved(
        ref, family, weights, dtype, form):
    """``SambaYLM`` calls the shared Mamba-1 mixer (``nlp/mamba.py``, which
    ``JambaLM`` calls too) where it held its own: the same operations in
    the same order, so not one bit of a logit may differ (``served``:
    since PR 39's recording, which lies within the file's tolerances of
    PR 37's: :func:`_golden`).  The recording
    is one machine's arithmetic: ``canary`` (the plain reference's logits,
    code this PR did not touch, recorded beside it) says whether this
    machine's CPU rounds as that one did; where it does not (on the host
    of the machine with the chip, another CPU, all four cases differ from
    the recording), the logits are held to the file's tolerances and the
    case reads SKIPPED, so that a run says which machines checked
    equality."""
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    with np.load(GOLDEN) as want:
        if form == "served":
            assert np.abs(want[f"served_{dtype}"]
                          - want[f"served_{dtype}_pr37"]).max() < tol
        got = _golden(family, weights, dtype)[form]
        canary = np.asarray(ref.logits(TINY, weights, _prompts([24])[0]))
        if np.array_equal(canary, want["canary"]):
            np.testing.assert_array_equal(got, want[f"{form}_{dtype}"])
            return
        assert np.abs(got - want[f"{form}_{dtype}"]).max() < tol
    pytest.skip("this machine's CPU rounds unlike the one that recorded "
                "the fixture (the canary differs): equality not checked, "
                "the logits lie within the file's tolerances")
