"""The seam between a served model and ``ContinuousBatcher``, held for
every served model alike: what ``nlp/served.py:ServedLM`` builds from a
model's ``cacheSpec()``, ``_prefillRawFn`` and ``pagedLogits`` is what
``remote/scheduler.py`` calls, and what the benchmark's traces find by
name (``jit_step``, ``jit_write``).  What a model computes is held by its
own tests; here only the convention."""
import numpy as np
import pytest

from deeplearning4j_tpu.remote import KVCachePool

PAGE, SLOTS, BUCKET, REAL = 4, 3, 8, 5


def _build(kind):
    if kind == "retrieval":
        from deeplearning4j_tpu.models.recsys import RetrievalLM
        table = np.random.RandomState(0).randn(64, 8).astype(np.float32)
        return RetrievalLM(table, table, maxLen=32)
    if kind == "transformer":
        from deeplearning4j_tpu.nlp import TransformerLM
        return TransformerLM(vocabSize=40, nLayers=1, nHeads=2, headSize=8,
                             maxLen=32, seed=5)
    from deeplearning4j_tpu import nlp
    cls, cfg = {"sambay": (nlp.SambaYLM, nlp.SambaYConfig),
                "olmo_hybrid": (nlp.OlmoHybridLM, nlp.OlmoHybridConfig),
                "pangu_moe": (nlp.PanguMoELM, nlp.PanguMoEConfig),
                "jamba": (nlp.JambaLM, nlp.JambaConfig),
                "keye_vl": (nlp.KeyeVLLM, nlp.KeyeVLConfig)}[kind]
    return cls(cfg(maxLen=32))


KINDS = ["transformer", "sambay", "olmo_hybrid", "pangu_moe", "jamba",
         "keye_vl", "retrieval"]
served = pytest.mark.parametrize("kind", KINDS)


def _pool(lm):
    pages = -(-lm.config.maxLen // PAGE)
    return KVCachePool.forSpec(lm.cacheSpec(), PAGE, 1 + SLOTS * pages,
                               SLOTS, pages)


def _step_args(lm, pool, toks, prev, pos, start):
    import jax.numpy as jnp
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    return (lm.params, *pool.arrays, i32(toks), i32(prev),
            jnp.asarray(pool.pageTable), i32(pos), i32(start))


def _admitted(lm, write, slot=1):
    """A pool in which ``slot`` holds one prompt of ``REAL`` tokens in a
    bucket of ``BUCKET``, written as the scheduler writes it, and the
    prompt's greedy token."""
    import jax.numpy as jnp
    pool = _pool(lm)
    pool.ensure(slot, BUCKET + 1)
    prompt = np.zeros((1, BUCKET), np.int32)
    prompt[0, BUCKET - REAL:] = np.random.RandomState(3).randint(
        1, lm.config.vocabSize, REAL)
    logits, *parts = lm.prefillRaw(prompt, lengths=[REAL])
    assert len(parts) == len(pool.arrays)
    ids = jnp.asarray(pool.heldIds(slot)[:BUCKET // PAGE], jnp.int32)
    pool.arrays = write(
        *pool.arrays, *(p[:, 0] for p in parts), ids,
        jnp.asarray(slot, jnp.int32))
    return pool, int(np.argmax(np.asarray(logits[0])))


@served
def test_the_step_takes_the_pools_arrays_and_donates_them(kind):
    import jax
    lm = _build(kind)
    pool = _pool(lm)
    n, cols = len(pool.arrays), 1 + len(lm.stepCounters)
    assert n == len(lm.cacheSpec().arrayKinds)
    zeros = np.zeros(SLOTS, np.int32)
    lowered = lm.buildPagedDecodeFn().lower(*_step_args(
        lm, pool, np.zeros((SLOTS, 1)), np.zeros((SLOTS, cols)), zeros,
        zeros))
    assert "jit_step" in lowered.as_text()[:400]
    args, kwargs = lowered.args_info
    assert not kwargs and len(args) == 1 + n + 5
    donated = [{a.donated for a in jax.tree.leaves(arg)} for arg in args]
    assert donated == [{False}] + [{True}] * n + [{False}] * 5
    out, *arrays = lowered.out_info
    assert out.shape == (SLOTS, cols) and out.dtype == np.int32
    assert [(a.shape, a.dtype) for a in arrays] == \
        [(a.shape, a.dtype) for a in pool.arrays]


@served
def test_prev_feeds_a_slot_whose_token_is_minus_one(kind):
    """The step before's output, all its columns, stands in for the
    host's token; what lies in the counters' columns is not read."""
    lm = _build(kind)
    cols = 1 + len(lm.stepCounters)
    pos, start = [0, BUCKET, 0], [0, BUCKET - REAL, 0]
    step, write = lm.buildPagedDecodeFn(), lm.buildPagedPrefillWriteFn()
    outs = []
    for fromPrev in (False, True):
        pool, first = _admitted(lm, write)
        toks = np.zeros((SLOTS, 1), np.int32)
        prev = np.full((SLOTS, cols), 7, np.int32)
        (prev if fromPrev else toks)[1, 0] = first
        if fromPrev:
            toks[1, 0] = -1
        before = pool.arrays
        out, *pool.arrays = step(*_step_args(lm, pool, toks, prev, pos,
                                             start))
        assert len(pool.arrays) == len(before)
        assert all(a.is_deleted() for a in before)     # donated
        outs.append(np.asarray(out))
    assert outs[0].shape == (SLOTS, cols)
    np.testing.assert_array_equal(outs[0], outs[1])
    assert 0 <= outs[0][1, 0] < lm.config.vocabSize
    # the counts ride in row 0 alone
    assert not outs[0][1:, 1:].any()


@served
def test_built_functions_are_named_and_fresh_a_build(kind):
    lm = _build(kind)
    steps = [lm.buildPagedDecodeFn() for _ in range(2)]
    writes = [lm.buildPagedPrefillWriteFn() for _ in range(2)]
    assert {f.__name__ for f in steps} == {"step"}
    assert {f.__name__ for f in writes} == {"write"}
    assert steps[0] is not steps[1] and writes[0] is not writes[1]
    assert steps[0].__wrapped__ is not steps[1].__wrapped__
    # built, not compiled: nothing counts until a call
    assert all(f._cache_size() == 0 for f in steps + writes)
    assert lm.compileCacheSize() == 0


@served
def test_a_prompt_past_the_capacity_raises_the_one_message(kind):
    lm = _build(kind)
    t = lm.config.maxLen + PAGE
    for prefill in (lm.prefillRaw, lm.restartFromPrompt):
        with pytest.raises(ValueError, match=(
                f"^prompt length {t} exceeds the capacity "
                f"{lm.config.maxLen}$")):
            prefill(np.zeros((1, t), np.int32), lengths=[3])
    assert lm.compileCacheSize() == 0       # refused before any trace


@served
def test_the_compile_count_sees_built_jits_only_and_drop_empties_it(kind):
    lm = _build(kind)
    assert lm.compileCacheSize() == 0
    assert "_prefillRawFn" not in vars(lm)      # counting builds nothing
    lm.prefillRaw(np.ones((1, BUCKET), np.int32), lengths=[REAL])
    assert lm.compileCacheSize() == 1
    # a restart is the same dispatch: the same executable
    lm.restartFromPrompt(np.ones((1, BUCKET), np.int32), lengths=[REAL])
    assert lm.compileCacheSize() == 1
    lm.prefillRaw(np.ones((1, 2 * BUCKET), np.int32))
    assert lm.compileCacheSize() == 2
    lm.dropCompiled()
    assert lm.compileCacheSize() == 0
    assert not {"_fwd", "_prefillRawFn"} & set(vars(lm))
