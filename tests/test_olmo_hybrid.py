"""The Olmo-Hybrid served LM (``nlp/olmo_hybrid.py``: three Gated-DeltaNet
linear-attention layers to every full-attention layer) against the
benchmark's plain reference, at a small size on the CPU: the chunked
delta rule against the token-by-token recurrence, the full forward on
logits, then prefill + decode through the scheduler's cache manager
holding pages and a matrix-valued recurrent state side by side.

The reference is ``benchmark/references/olmohybrid.py`` itself, loaded by
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

# two periods: linear, linear, linear, full, twice
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "intermediate_size": 128,
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "num_hidden_layers": 8,
        "vocab_size": 96, "rms_norm_eps": 1e-6, "delta_chunk": 8,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2}
PAGE, SLOTS, CAP = 4, 3, 64

# float32 weights on the CPU: both sides compute in float32 and differ in
# the order of their sums (the program folds a chunk's rank-one updates
# into matmuls, the reference runs the recurrence) -- measured 1.1e-5 on
# logits whose spread is 0.16, some 20 ulp after 8 layers of two norms each
TOL_F32 = 5e-5
# bfloat16 weights: the program rounds the residual stream, K/V and every
# matmul's input to 8 bits of mantissa at each of 8 layers where the
# reference keeps float32, and at this width the norms' 1e-6 weighs in
# (a mixer's output has a mean square near it); measured 0.085 (mean
# 0.014), and float8 inputs read 0.84 (mean 0.18)
TOL_BF16 = 0.25


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
    return _load("benchmark/references/olmohybrid.py",
                 "bench_ref_olmohybrid")


@pytest.fixture(scope="module")
def family():
    return _load("benchmark/configs/olmohybrid.py", "bench_cfg_olmohybrid")


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


# -- the chunked delta rule against the recurrence ---------------------------
@pytest.mark.parametrize("case", ["beta_above_1_weak_decay", "strong_decay",
                                  "mixed", "left_padded"])
@pytest.mark.parametrize("chunk", [4, 64])
def test_chunked_delta_rule_is_the_recurrence(ref, case, chunk):
    """Outputs and the END STATE of ``delta_rule_chunked`` against the
    reference's token-by-token scan, at a length (37) that is no multiple
    of either chunk.  Float32 on both sides: what differs is the order of
    the sums (measured 4e-6 at outputs of size 3 to 6)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.delta import delta_rule_chunked
    rs = np.random.RandomState(0)
    b, T, H, dk, dv = 2, 37, 3, 8, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(rs.randn(b, T, H, dk)), unit(rs.randn(b, T, H, dk))
    v = rs.randn(b, T, H, dv)
    beta, alpha = {
        "beta_above_1_weak_decay": (rs.uniform(1.0, 2.0, (b, T, H)),
                                    rs.uniform(0.95, 1.0, (b, T, H))),
        # 64 steps at 1e-3 underflow float32: gamma is 0 at a chunk's end
        "strong_decay": (rs.uniform(0.0, 2.0, (b, T, H)),
                         rs.uniform(1e-3, 0.3, (b, T, H))),
        "mixed": (rs.uniform(0.0, 2.0, (b, T, H)),
                  rs.uniform(1e-4, 1.0, (b, T, H))),
        "left_padded": (rs.uniform(0.0, 2.0, (b, T, H)),
                        rs.uniform(0.5, 1.0, (b, T, H)))}[case]
    real = np.ones((b, T, 1))
    if case == "left_padded":
        # as the prefill pads: beta 0, alpha 1, q, k, v zero before the
        # first real position (11 and 30 here)
        real = (np.arange(T)[None, :] >= np.array([11, 30])[:, None]
                )[..., None].astype(np.float64)
        beta, alpha = beta * real, alpha ** real
        q, k, v = (a * real[..., None] for a in (q, k, v))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, S = delta_rule_chunked(f32(q), f32(k), f32(v), f32(beta),
                              jnp.log(f32(alpha)), chunk)
    for i in range(b):
        first = int(np.argmax(real[i, :, 0]))
        want_o, want_S = ref.delta_rule(*(f32(a[i, first:]) for a in (
            q, k, v, beta, alpha)))
        assert np.abs(np.asarray(o[i, first:]) - want_o).max() < 2e-5
        assert np.abs(np.asarray(S[i]) - want_S).max() < 2e-5
        assert np.abs(np.asarray(o[i, :first])).max(initial=0.0) == 0.0


def test_layer_kinds_follow_the_published_pattern(ref, family):
    kinds = family.program_config(TINY, CAP).layerKinds()
    assert kinds == (["linear"] * 3 + ["full"]) * 2
    assert ref.layer_kinds(TINY) == TINY["layer_types"]
    with pytest.raises(ValueError):
        family.program_config(dict(TINY, layer_types=["full_attention"]
                                   + TINY["layer_types"][1:]), CAP)


@pytest.mark.parametrize("dtype,tol", [("float32", TOL_F32),
                                       ("bfloat16", TOL_BF16)])
def test_full_forward_matches_the_reference_logits(ref, family, weights,
                                                   dtype, tol):
    import jax
    lm = _lm(family, weights, dtype)
    w = jax.tree.map(lambda a: a.astype(dtype), weights)
    toks = _prompts([29])[0]                    # no multiple of the chunk
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
    pages and recurrent state: a ragged left-padded prompt (the chunked
    form's end state is what the recurrence continues from), 40 new
    tokens, then THE SAME SLOT reused by a shorter sequence in another
    bucket whose stale pages, delta state and convolution window must not
    reach it; the idle slots' state is left as it was."""
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
    # slot 0 never held a sequence: the steps left its state untouched
    for before, a in zip(idle, pool.arrays[2:]):
        np.testing.assert_array_equal(before, np.asarray(a[:, 0]))
    assert pool.usedPages() == 0 and pool.stateSlots() == 0


@pytest.fixture
def batcher(family, weights):
    from deeplearning4j_tpu.remote import BucketLadder, ContinuousBatcher
    cb = ContinuousBatcher(
        _lm(family, weights, "float32"), name="olmo", maxSlots=SLOTS,
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
    different steps beside running neighbours and two slots are reused
    after a retirement.  Every served token must be the reference's best
    up to float32 rounding of logits (``TOL_F32``); then the manager's
    books are empty and the telemetry of a model with recurrent state and
    NO ring is whole."""
    from deeplearning4j_tpu.telemetry import serving_metrics, tracer
    sm = serving_metrics()
    count = lambda c, **kw: c.value(model="olmo", **kw) or 0
    before = (count(sm.prefill_prompt_tokens()),
              sum(count(sm.prefill_positions(), bucket=str(b))
                  for b in (8, 16)))
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
    # pages for the TWO full layers, rows of all four heads side by side;
    # the six linear layers' state beside them, and no ring
    assert pool.k.shape == (2, pool.numPages, PAGE, 64)
    assert [a.shape for a in pool.arrays[2:]] == [
        (6, SLOTS, 4, 8, 16), (6, SLOTS, 3, 4 * (8 + 8 + 16))]
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert pool.freePages() == pool.numPages - 1
    assert any(e["name"] == "serving.state.write" for e in tracer().events())
    assert sm.state_slots_in_use().value(model="olmo") == 0
    assert sm.cache_bytes().value(model="olmo", kind="paged") == 0
    assert sm.cache_bytes().value(model="olmo", kind="recurrent") == 0
    assert sm.ring_rows_in_use().value(model="olmo") == 0
    assert sm.ring_attention_kernel().value(model="olmo") == 0
    # padded positions and real tokens of the five prefills: their ratio
    # is what the bucket ladder wastes
    assert count(sm.prefill_prompt_tokens()) - before[0] == 5 + 11 + 16 + 7 + 3
    assert sum(count(sm.prefill_positions(), bucket=str(b))
               for b in (8, 16)) - before[1] == 8 + 16 + 16 + 8 + 8


def test_preempt_replay_and_evacuate_return_the_same_tokens(ref, weights,
                                                            batcher):
    """A preempted sequence restarts from its prompt: prefill rebuilds
    pages, delta state and convolution window, the replay is
    teacher-forced, and the client sees each token once.  ``evacuate``
    hands the sequences over reset the same way."""
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


def test_admission_behind_an_unread_step_that_wrote_the_slots_state(
        ref, weights, batcher):
    """The loop is one step ahead, and Y is admitted into X's slot while
    a step that wrote X's delta state, convolution window and page row is
    still unread (X's quota ends: its slot is free from the dispatch of
    its last step); device order puts Y's admission write behind it, so Y
    and its neighbours get the reference's tokens and the books are
    empty.  Iterated by hand, so no clock decides what is unread when."""
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
    (ga, sa), (gx, sx), (gz, sz) = stream(pa), stream(px, 5), stream(pz)
    for _ in range(4):
        batcher._iterate()
    assert batcher._inflight.seqs == [sa, sx, sz]
    assert batcher._parted == [sx]
    gy, sy = stream(py)
    assert batcher._slotSeq == [sa, None, sz]
    batcher._iterate()          # Y's admission, behind that unread step
    assert batcher._slotSeq == [sa, sy, sz]
    while not batcher._idle():
        batcher._iterate()
    for p, g, n in ((pa, ga, 30), (pz, gz, 30), (py, gy, 30), (px, gx, 5)):
        toks = list(g)
        assert len(toks) == n
        assert _served_gap(ref, weights, p, toks) < TOL_F32
    pool = batcher.pool
    assert pool.usedPages() == 0 and pool.stateSlots() == 0
    assert batcher._inflight is None and batcher._parted == []


def test_each_prompt_bucket_prefills_under_its_own_name(family, weights):
    """The device trace tells a bucket's prefill from another's by the
    program's name, which ``prefill_mfu_pct.docqa`` counts operations by
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
    """``jax.eval_shape`` of the published sizes: 7.43 B parameters whole
    (the model card says 7 B), 4.10 B in the 16 layers the benchmark's
    configuration keeps, every width as published."""
    import jax
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmo_hybrid_7b.json")) as f:
        config = json.load(f)
    assert [r.split()[0] for r in config["reduced"]] == [
        "num_hidden_layers", "layer_types"]
    empty = {"emb": None, "head": None, "norm_f": None, "layers": []}
    lm = family.build_lm(config, empty, config["serving"]["capacity"])
    kinds = lm.config.layerKinds()
    assert (kinds.count("linear"), kinds.count("full")) == (12, 4)
    shapes = jax.eval_shape(lm._init_params)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == ref.param_count(config) == 4_100_788_944
    assert all(a.dtype == "bfloat16" for a in jax.tree.leaves(shapes))
    whole = dict(config, num_hidden_layers=32,
                 layer_types=config["layer_types"] * 2)
    assert ref.param_count(whole) == 7_430_870_688
    spec = lm.cacheSpec()
    assert (spec.pagedLayers, spec.ringLayers, spec.rowWidth) == (4, 0, 3840)
    assert [(n, s, np.dtype(t)) for n, s, t in spec.slotState] == [
        ("delta", (12, 30, 96, 192), np.dtype("float32")),
        ("conv", (12, 3, 11520), np.dtype("bfloat16"))]


# -- the delta rule moved to nlp/delta.py (PR 44) ----------------------------
GOLDEN = os.path.join(REPO, "tests", "fixtures", "olmo_pangu_logits_pr43.npz")


def _delta_case():
    rs = np.random.RandomState(5)
    b, T, H, dk, dv = 2, 37, 3, 8, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    return tuple(np.asarray(a, np.float32) for a in (
        unit(rs.randn(b, T, H, dk)), unit(rs.randn(b, T, H, dk)),
        rs.randn(b, T, H, dv), rs.uniform(0.0, 2.0, (b, T, H)),
        np.log(rs.uniform(1e-3, 1.0, (b, T, H)))))


def _golden(family, weights, dtype):
    """What ``fixtures/olmo_pangu_logits_pr43.npz`` holds of this model
    for ``dtype`` (keys ``olmo_<form>_<dtype>``): the full forward's
    logits of a 24-token prompt, the logits of a left-padded prefill (11
    tokens in the 16 bucket) and of 12 teacher-forced steps through the
    pool, and ``delta_rule_chunked``'s outputs and end state on
    :func:`_delta_case` at chunks of 8.  Recorded on commit 424521a (PR
    43), where the rule still lay in ``olmo_hybrid.py``, by ``np.savez``
    over this function and ``test_pangu_moe._golden``, with
    ``olmo_canary`` = ``ref.logits(TINY, weights, _prompts([24])[0])`` of
    the same machine."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nlp.delta import delta_rule_chunked
    from deeplearning4j_tpu.remote import KVCachePool
    lm = _lm(family, weights, dtype)
    forward = np.asarray(lm.forward(np.asarray([_prompts([24])[0]])))[0]
    pool = KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * (CAP // PAGE),
                               SLOTS, CAP // PAGE)
    served = np.stack(list(_teacher_forced(
        lm, pool, lm.buildPagedPrefillWriteFn(), jax.jit(lm.pagedLogits), 1,
        _prompts([11])[0], 16, _prompts([12], seed=7)[0])))
    o, S = delta_rule_chunked(*(jnp.asarray(a) for a in _delta_case()), 8)
    return {"forward": forward, "served": served,
            "rule": np.concatenate([np.asarray(o).ravel(),
                                    np.asarray(S).ravel()])}


@pytest.mark.parametrize("form", ["forward", "served", "rule"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_logits_are_bit_for_bit_what_they_were_before_the_rule_moved(
        ref, family, weights, dtype, form):
    """``nlp/delta.py`` computes what ``olmo_hybrid.py`` computed, in the
    same order, so not one bit of a logit (or of the rule's own outputs)
    may differ from the recording of PR 43's tree.  The recording is one
    machine's arithmetic: where this machine's CPU rounds unlike it (the
    canary, the plain reference's logits, differs), the values are held
    to the file's tolerances and the case reads SKIPPED (as
    ``test_sambay.py``'s does)."""
    tol = TOL_F32 if dtype == "float32" or form == "rule" else TOL_BF16
    with np.load(GOLDEN) as want:
        got = _golden(family, weights, dtype)[form]
        canary = np.asarray(ref.logits(TINY, weights, _prompts([24])[0]))
        if np.array_equal(canary, want["olmo_canary"]):
            np.testing.assert_array_equal(got, want[f"olmo_{form}_{dtype}"])
            return
        assert np.abs(got - want[f"olmo_{form}_{dtype}"]).max() < tol
    pytest.skip("this machine's CPU rounds unlike the one that recorded "
                "the fixture (the canary differs): equality not checked, "
                "the values lie within the file's tolerances")
